"""Engine prefill wait: for each request whose prefill (the program's
``executor.prefill`` span) starts in the traced window, that start minus
the request's ``engine.submit`` (`repro.serving.telemetry`), mean in ms.
None where the program keeps no such records."""


def read(rec):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    if not rec.spans:
        return None
    w0 = min(s for _, s, _, _ in rec.spans)
    w1 = max(e for _, _, e, _ in rec.spans)
    records = telemetry.records()
    submits = {}
    for r in records:
        if r.name == "engine.submit":
            submits.setdefault(r.rid, []).append(r.start)
    waits = []
    for r in records:
        if r.name == "executor.prefill" and w0 <= r.start <= w1:
            before = [t for t in submits.get(r.rid, ()) if t <= r.start]
            if before:
                waits.append(r.start - max(before))
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
