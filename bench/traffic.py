"""The one traffic generator: reads a mix's parameters and makes requests
from ``--seed``.

Every request is a function of ``(seed, rid)`` alone: its adapter and its
prompt tokens.  Lengths are the mix's, the same for every seed, so seeds
change which adapters and tokens are served and never how much work there is.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

# each draw from (seed, rid) gets its own stream, so adding a field never
# moves another
_PROMPT, _ADAPTER = 0, 1


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng((seed, rid, _PROMPT))
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def adapter_id(seed: int, rid: int, traffic: Dict) -> int:
    ad = traffic["adapters"]
    if ad.get("popularity", "uniform") != "uniform":
        raise ValueError(f"unknown adapter popularity {ad['popularity']!r}")
    rng = np.random.default_rng((seed, rid, _ADAPTER))
    return int(rng.integers(0, ad["count"]))


class ClosedLoop:
    """``clients`` callers, each of which sends its next request as soon as
    its last one has finished.  Request ids count up from 0 in the order
    the requests are sent."""

    def __init__(self, traffic: Dict, seed: int):
        if traffic.get("loop") != "closed":
            raise ValueError(f"unknown loop {traffic.get('loop')!r}")
        self.traffic = traffic
        self.seed = seed
        self.clients = int(traffic["clients"])
        self.prompt_len = int(traffic["prompt_len"])
        self.output_len = int(traffic["output_len"])
        self.next_rid = 0

    def send(self) -> Dict:
        """The next request's parameters: ``rid``, ``adapter``,
        ``prompt_len`` and ``output_len``."""
        rid = self.next_rid
        self.next_rid += 1
        return {"rid": rid, "adapter": adapter_id(self.seed, rid, self.traffic),
                "prompt_len": self.prompt_len, "output_len": self.output_len}

    def first_wave(self) -> List[Dict]:
        return [self.send() for _ in range(self.clients)]
