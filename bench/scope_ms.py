"""Device milliseconds per decode step in one named scope of the fused
decode program: the program's ``scope_s`` time whose scope path holds the
scope (`bench.trace.in_scope`) over the window's decode steps."""
from bench import trace

PROGRAM = "jit__fused_decode_fn"


def per_step(rec, scope: str):
    steps = rec.of("decode")
    by = rec.reduced.get("scope_s", {}).get(PROGRAM, {})
    secs = trace.in_scope(by, scope)
    if not steps or secs <= 0:
        return None
    return 1e3 * secs / len(steps)
