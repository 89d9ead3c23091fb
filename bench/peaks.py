"""Published peaks of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A chip that is not here is an error, never a default."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
