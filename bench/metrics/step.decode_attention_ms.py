"""Device time per decode step in the fused decode program's `attention`
scope (rope, the KV write, the fused attention kernel, the o-projection and
its adapter delta), from ``scope_s`` (ms)."""
from bench import scope_ms


def read(rec):
    return scope_ms.per_step(rec, "attention")
