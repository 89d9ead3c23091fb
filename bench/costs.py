"""Operations and bytes of the served work, counted from shapes: the least
the work needs, whatever implements it.

Attention counts the tokens actually attended (``kv_len``), not a padded
window; adapters count each distinct adapter's (or cluster's) factors once
per step.  Everything is bf16 (2 bytes) except the fused kernel's float32
delta output.  Sizes come from the configuration file's published keys.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Sequence, Tuple

BF16, F32 = 2, 4


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    H: int
    Kv: int
    hd: int
    dff: int
    L: int
    vocab: int

    @classmethod
    def of(cls, conf: Dict) -> "Arch":
        H = conf["num_attention_heads"]
        return cls(d=conf["hidden_size"], H=H,
                   Kv=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or conf["hidden_size"] // H,
                   dff=conf["intermediate_size"],
                   L=conf["num_hidden_layers"], vocab=conf["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        d, H, Kv, hd = self.d, self.H, self.Kv, self.hd
        return 2 * d * H * hd + 2 * d * Kv * hd + 3 * d * self.dff

    def target_dims(self, t: str) -> Tuple[int, int]:
        qd, kvd = self.H * self.hd, self.Kv * self.hd
        return {"q": (self.d, qd), "k": (self.d, kvd), "v": (self.d, kvd),
                "o": (qd, self.d)}[t]


def adapter_token_flops(a: Arch, ad: Dict, targets: Iterable[str]) -> int:
    """Adapter FLOPs for one token in one layer over ``targets``."""
    r = ad["rank"]
    f = 0
    for t in targets:
        di, do = a.target_dims(t)
        f += 2 * r * (di + do) + (2 * r * r if ad["mode"] == "jd" else 0)
    return f


def adapter_layer_bytes(a: Arch, ad: Dict, targets: Iterable[str],
                        ids: Sequence[int]) -> int:
    """Least adapter bytes one layer reads for a batch served by ``ids``:
    each distinct adapter's factors (lora) or each distinct cluster's
    basis plus each distinct adapter's Sigma (jd)."""
    r = ad["rank"]
    uniq = set(int(i) for i in ids)
    k = int(ad.get("clusters", 1))
    clusters = {i % k for i in uniq}
    b = 0
    for t in targets:
        di, do = a.target_dims(t)
        if ad["mode"] == "lora":
            b += len(uniq) * r * (di + do) * BF16
        else:
            b += (len(clusters) * r * (di + do) + len(uniq) * r * r) * BF16
    return b


def decode_step_flops(a: Arch, ad: Dict, kv_lens: Sequence[int]) -> int:
    """Model FLOPs of one decode step: one token for each active request,
    request b attending ``kv_lens[b]`` tokens (its new one included)."""
    per_tok = a.L * (2 * a.layer_matmul_params
                     + adapter_token_flops(a, ad, ad["targets"])) \
        + 2 * a.d * a.vocab
    attn = sum(a.L * 4 * a.H * a.hd * kv for kv in kv_lens)
    return len(kv_lens) * per_tok + attn


def prefill_flops(a: Arch, ad: Dict, prompt_len: int) -> int:
    """Model FLOPs of one request's prefill: causal attention over the
    prompt and logits for its last position only."""
    P = prompt_len
    per_tok = 2 * a.layer_matmul_params + adapter_token_flops(
        a, ad, ad["targets"])
    attn = 4 * a.H * a.hd * P * (P + 1) // 2
    return a.L * (P * per_tok + attn) + 2 * a.d * a.vocab


def decode_step_bytes(a: Arch, ad: Dict, kv_lens: Sequence[int],
                      ids: Sequence[int]) -> int:
    """Least bytes one decode step reads: every weight once (the head
    over the real vocabulary), the K/V of every attended token, and the
    batch's adapter factors."""
    weights = (a.L * a.layer_matmul_params + a.d * a.vocab) * BF16
    kv = a.L * sum(2 * kv * a.Kv * a.hd * BF16 for kv in kv_lens)
    return weights + kv + a.L * adapter_layer_bytes(a, ad, ad["targets"], ids)


def fused_decode_call(a: Arch, ad: Dict, kv_lens: Sequence[int],
                      ids: Sequence[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one call of the fused decode kernel (one layer):
    attention of each request over its ``kv_len`` tokens plus the
    o-projection adapter delta."""
    B = len(kv_lens)
    qd = a.H * a.hd
    flops = sum(4 * qd * kv for kv in kv_lens) \
        + B * adapter_token_flops(a, ad, ["o"])
    nbytes = sum(2 * kv * a.Kv * a.hd * BF16 for kv in kv_lens) \
        + 2 * B * qd * BF16 + B * a.d * F32 \
        + adapter_layer_bytes(a, ad, ["o"], ids)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The roofline: the larger of compute and memory time, and which."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
