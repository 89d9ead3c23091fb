"""Dense decoder with grouped-query attention (Qwen3, Mistral): how its
configuration file maps onto the program, the shapes of its adapter
targets, and the operations and bytes of its served work.

Counts are the least the work needs, whatever implements it: attention
counts the tokens actually attended (``kv_len``), not a padded window;
adapters count each distinct adapter's (or cluster's) factors once per
step (`bench.costs`).  Everything is bf16 (2 bytes) except the fused
kernel's float32 delta output.  Sizes come from the configuration file's
published keys.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench.costs import BF16, F32, adapter_layer_bytes, adapter_token_flops

# the fused decode kernels, one call per layer in each decode step
FUSED_DECODE = ("fused_decode_jd", "fused_decode_lora")


def model_config(conf: Dict, traffic: Dict):
    """The program's `ModelConfig` for a configuration file, keyed by the
    published ``config.json`` names."""
    from repro.configs.base import LoRAConfig, ModelConfig

    prog = conf["program"]
    ad = traffic["adapters"]
    return ModelConfig(
        name=conf["name"], family=prog["family"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim")
        or conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=prog["qk_norm"], rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        sliding_window=conf.get("sliding_window") or 0,
        lora=LoRAConfig(rank=ad["rank"], targets=tuple(ad["targets"])))


def adapter_dims(conf: Dict) -> Dict[str, Tuple[int, int]]:
    """``{target: (d_in, d_out)}`` of the adapter targets q, k, v and o."""
    a = arch(conf)
    return {t: a.target_dims(t) for t in ("q", "k", "v", "o")}


def arch(conf: Dict) -> "Arch":
    """The cost object the per-layer metrics read (``rec.arch``)."""
    H = conf["num_attention_heads"]
    return Arch(d=conf["hidden_size"], H=H, Kv=conf["num_key_value_heads"],
                hd=conf.get("head_dim") or conf["hidden_size"] // H,
                dff=conf["intermediate_size"], L=conf["num_hidden_layers"],
                vocab=conf["vocab_size"])


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    H: int
    Kv: int
    hd: int
    dff: int
    L: int
    vocab: int

    @property
    def layer_matmul_params(self) -> int:
        d, H, Kv, hd = self.d, self.H, self.Kv, self.hd
        return 2 * d * H * hd + 2 * d * Kv * hd + 3 * d * self.dff

    def target_dims(self, t: str) -> Tuple[int, int]:
        qd, kvd = self.H * self.hd, self.Kv * self.hd
        return {"q": (self.d, qd), "k": (self.d, kvd), "v": (self.d, kvd),
                "o": (qd, self.d)}[t]

    def decode_step_flops(self, ad: Dict, kv_lens: Sequence[int]) -> int:
        """Model FLOPs of one decode step: one token for each active
        request, request b attending ``kv_lens[b]`` tokens (its new one
        included)."""
        per_tok = self.L * (2 * self.layer_matmul_params
                            + adapter_token_flops(self, ad, ad["targets"])) \
            + 2 * self.d * self.vocab
        attn = sum(self.L * 4 * self.H * self.hd * kv for kv in kv_lens)
        return len(kv_lens) * per_tok + attn

    def prefill_flops(self, ad: Dict, prompt_len: int) -> int:
        """Model FLOPs of one request's prefill: causal attention over the
        prompt and logits for its last position only."""
        P = prompt_len
        per_tok = 2 * self.layer_matmul_params + adapter_token_flops(
            self, ad, ad["targets"])
        attn = 4 * self.H * self.hd * P * (P + 1) // 2
        return self.L * (P * per_tok + attn) + 2 * self.d * self.vocab

    def decode_step_bytes(self, ad: Dict, kv_lens: Sequence[int],
                          ids: Sequence[int]) -> int:
        """Least bytes one decode step reads: every weight once (the head
        over the real vocabulary), the K/V of every attended token, and the
        batch's adapter factors."""
        weights = (self.L * self.layer_matmul_params
                   + self.d * self.vocab) * BF16
        kv = self.L * sum(2 * kv * self.Kv * self.hd * BF16 for kv in kv_lens)
        return weights + kv + self.L * adapter_layer_bytes(
            self, ad, ad["targets"], ids)

    def fused_decode_call(self, ad: Dict, kv_lens: Sequence[int],
                          ids: Sequence[int]) -> Tuple[int, int]:
        """(FLOPs, bytes) of one call of the fused decode kernel (one
        layer): attention of each request over its ``kv_len`` tokens plus
        the o-projection adapter delta."""
        B = len(kv_lens)
        qd = self.H * self.hd
        flops = sum(4 * qd * kv for kv in kv_lens) \
            + B * adapter_token_flops(self, ad, ["o"])
        nbytes = sum(2 * kv * self.Kv * self.hd * BF16 for kv in kv_lens) \
            + 2 * B * qd * BF16 + B * self.d * F32 \
            + adapter_layer_bytes(self, ad, ["o"], ids)
        return flops, nbytes

    def kernel_calls(self, kernel: str, ad: Dict, kv_lens: Sequence[int],
                     ids: Sequence[int]) -> Optional[List[Tuple[int, int, int]]]:
        """The calls of the named kernel in one decode step, as
        ``[(FLOPs, bytes, calls)]`` groups of like calls; None for a kernel
        this architecture does not run."""
        if kernel not in FUSED_DECODE:
            return None
        return [self.fused_decode_call(ad, kv_lens, ids) + (self.L,)]
