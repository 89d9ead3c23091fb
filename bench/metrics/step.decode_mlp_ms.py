"""Device time per decode step in the fused decode program's `mlp` scope
(the gated MLP of every layer), from ``scope_s`` (ms)."""
from bench import scope_ms


def read(rec):
    return scope_ms.per_step(rec, "mlp")
