"""Engine host time per step: the wall time of each `ServingEngine.step()`
minus the executor calls inside it, mean over the window's steps (ms)."""


def read(rec):
    steps = rec.of("engine")
    calls = rec.of("prefill") + rec.of("decode")
    if not steps:
        return None
    host = 0.0
    for _, s, e, _ in steps:
        inner = sum(ce - cs for _, cs, ce, _ in calls if s <= cs and ce <= e)
        host += (e - s) - inner
    return 1e3 * host / len(steps)
