"""Device time per decode step in the fused decode program's `moe` scope
(the router, the held routed experts and the shared experts of every
expert layer), from ``scope_s`` (ms).  None for a program without it."""
from bench import scope_ms


def read(rec):
    return scope_ms.per_step(rec, "moe")
