"""The comparison that decides ``correct``.

After the window, a sample of the finished requests, drawn from the seed,
is run once through the plain float32 reference of the cell's
configuration: each prompt with the tokens the timed path served for it
(the prefill's token, then every decode step's).  At each served position
the reference gives its best logit; the number compared is the widest gap
by which a served token's reference logit lies below that best, over all
the sample's positions.  Greedy serving of a correct program picks the
reference's best up to rounding, so its gaps stay at the rounding's scale;
a wrong token, cache or adapter route puts a served token far below it.

Besides, every finished request must have exactly the mix's output length
of token ids, all inside the vocabulary.  The limits are the cell's
``bench/limits/<cell>.json``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import spec

_SAMPLE = 2          # the sample's own stream of (seed, _SAMPLE)
_SCORE_BYTES = 1 << 30   # attention scores one reference block may hold


def sample(served: List[Dict], seed: int, k: int) -> List[Dict]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not served:
        return []
    by_len = sorted(served, key=lambda r: (-len(r["tokens"]), r["rid"]))
    rest = by_len[1:]
    rng = np.random.default_rng((seed, _SAMPLE))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [by_len[0]] + [rest[i] for i in sorted(pick)]


def sequences(reqs: List[Dict]):
    """Token inputs (n, T) and judged tokens (n, N + 1) of equal-length
    requests: the inputs are the prompt, then each token fed back but the
    last, which nothing consumed.  What is fed back is the request's own
    tokens, or its ``fed`` list where it has one (the control's tokens are
    judged at the positions of the program's)."""
    judged = np.asarray([[r["first"]] + r["tokens"] for r in reqs], np.int64)
    inputs = np.asarray([np.concatenate([r["prompt"],
                                         np.asarray(r.get("fed", j))[:-1]])
                         for r, j in zip(reqs, judged)], np.int64)
    return inputs, judged


def blocks(n: int, heads: int, T: int):
    per = max(1, _SCORE_BYTES // (heads * T * T * 4))
    return [slice(i, min(n, i + per)) for i in range(0, n, per)]


def gaps(conf: Dict, mode: str, params, adapters,
         reqs: List[Dict]) -> np.ndarray:
    """Per judged position (n, N + 1), the reference's best logit minus its
    logit of the judged token."""
    import jax.numpy as jnp

    ref = spec.reference(conf["reference"])
    inputs, judged = sequences(reqs)
    P = len(reqs[0]["prompt"])
    ids = np.asarray([r["adapter"] for r in reqs], np.int32)
    out = []
    for b in blocks(len(reqs), conf["num_attention_heads"], inputs.shape[1]):
        lg = ref.logits(params, adapters, conf, mode, inputs[b], ids[b], P - 1)
        at = jnp.take_along_axis(lg, jnp.asarray(judged[b])[..., None],
                                 -1)[..., 0]
        out.append(np.asarray(jnp.max(lg, -1) - at))
    return np.concatenate(out)


def _well_formed(served: List[Dict], N: int, V: int):
    """The finished requests of exactly ``N`` tokens inside the vocabulary,
    and the others."""
    bad = [r for r in served
           if len(r["tokens"]) != N or r["first"] is None
           or not all(0 <= t < V for t in [r["first"]] + r["tokens"])]
    return [r for r in served if r not in bad], bad


def check(conf: Dict, traffic: Dict, limits: Dict, params, adapters,
          served: List[Dict], seed: int) -> Dict:
    """``correct``, the failed count, and each number compared beside its
    limit: ``widest_gap`` (at most its limit), ``bad_requests`` (finished
    requests of the wrong length or with ids outside the vocabulary, at
    most 0) and ``compared_tokens`` (at least its limit)."""
    N, V = int(traffic["output_len"]), int(conf["vocab_size"])
    good, bad = _well_formed(served, N, V)
    picked = sample(good, seed, int(traffic.get("check_requests", 1)))
    widest = (float(gaps(conf, traffic["adapters"]["mode"], params, adapters,
                         picked).max()) if picked else float("inf"))
    n_cmp = sum(len(r["tokens"]) + 1 for r in picked)
    checks = {
        "widest_gap": {"value": widest, "limit": limits["widest_gap"]["limit"]},
        "bad_requests": {"value": len(bad), "limit": 0},
        "compared_tokens": {"value": n_cmp,
                            "limit": limits["compared_tokens"]["limit"]},
    }
    ok = (widest <= checks["widest_gap"]["limit"] and not bad
          and n_cmp >= checks["compared_tokens"]["limit"])
    return {"correct": bool(ok), "failed": len(bad), "checks": checks}


def control(conf: Dict, traffic: Dict, params, adapters, served: List[Dict],
            seed: int, quant: str) -> List[Dict]:
    """The control put in the program's place: for the sample `check` draws,
    at each position of the same prompts and served tokens, the token the
    reference computed in ``quant`` puts first.  `check` judges the result
    like any served output."""
    import jax.numpy as jnp

    N, V = int(traffic["output_len"]), int(conf["vocab_size"])
    good, _ = _well_formed(served, N, V)
    picked = sample(good, seed, int(traffic.get("check_requests", 1)))
    if not picked:
        return []
    ref = spec.reference(conf["reference"])
    inputs, judged = sequences(picked)
    P = len(picked[0]["prompt"])
    ids = np.asarray([r["adapter"] for r in picked], np.int32)
    tok = np.concatenate([np.asarray(jnp.argmax(ref.logits(
        params, adapters, conf, traffic["adapters"]["mode"], inputs[b],
        ids[b], P - 1, quant), -1)) for b in blocks(
            len(picked), conf["num_attention_heads"], inputs.shape[1])])
    return [dict(r, first=int(t[0]), tokens=[int(x) for x in t[1:]],
                 fed=[int(x) for x in j])
            for r, t, j in zip(picked, tok, judged)]
