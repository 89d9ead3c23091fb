"""Executor decode fetch: the copy of each decode step's token ids to the
host once the device has them ready, the program's
``executor.decode.fetch`` span (`repro.serving.telemetry`) inside the
traced window, mean over the window's ``executor.decode`` steps (ms).
None where the program keeps no such records."""

PART = "executor.decode.fetch"


def read(rec):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    if not rec.spans:
        return None
    w0 = min(s for _, s, _, _ in rec.spans)
    w1 = max(e for _, _, e, _ in rec.spans)
    inside = [r for r in telemetry.records() if w0 <= r.start and r.end <= w1]
    steps = sum(1 for r in inside if r.name == "executor.decode")
    if not steps:
        return None
    return 1e3 * sum(r.end - r.start for r in inside if r.name == PART) / steps
