"""Executor decode launch: host time of each decode step before its
program is on the device, the program's ``executor.decode.inputs`` and
``executor.decode.launch`` spans (`repro.serving.telemetry`) inside the
traced window, summed over the window's ``executor.decode`` steps and
averaged over them (ms).  None where the program keeps no such records."""

PARTS = ("executor.decode.inputs", "executor.decode.launch")


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
    return 1e3 * sum(r.end - r.start for r in inside if r.name in PARTS) / steps
