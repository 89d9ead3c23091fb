"""Operations and bytes shared by every architecture: the adapters' work,
counted from the target shapes an architecture's cost object gives
(``target_dims``), and the roofline.

An architecture's own counts (a decode step, a prefill, its kernels' calls)
live in its module, ``bench/archs/<name>.py``.  Adapters count each
distinct adapter's (or cluster's) factors once per step; everything is bf16
(2 bytes) unless said otherwise.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

BF16, F32 = 2, 4


def adapter_token_flops(a, ad: Dict, targets: Iterable[str]) -> int:
    """Adapter FLOPs for one token in one layer over ``targets``."""
    r = ad["rank"]
    f = 0
    for t in targets:
        di, do = a.target_dims(t)
        f += 2 * r * (di + do) + (2 * r * r if ad["mode"] == "jd" else 0)
    return f


def adapter_layer_bytes(a, ad: Dict, targets: Iterable[str],
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


def least_seconds(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The roofline: the larger of compute and memory time, and which."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
