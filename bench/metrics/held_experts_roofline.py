"""Roofline share of the held routed experts in the fused decode program:
their least time over the device time of scope ``moe/experts`` (%).

Each decode step's ``executor.decode.experts`` event
(`repro.serving.telemetry`) inside the traced window counts, summed over
the expert layers, the (token, expert) pairs routed to the experts held
here (``routed``) and the held experts with at least one (``used``); the
architecture's ``experts_least`` turns them into FLOPs and bytes, and the
least time of a step is the larger of the two over the chip's peaks.  None
where the program keeps no such events or has no such scope."""
from bench import costs, scope_ms

SCOPE = "moe/experts"


def read(rec):
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    if not rec.spans or not hasattr(rec.arch, "experts_least"):
        return None
    w0 = min(s for _, s, _, _ in rec.spans)
    w1 = max(e for _, _, e, _ in rec.spans)
    events = [r for r in telemetry.records()
              if r.name == "executor.decode.experts" and w0 <= r.start <= w1]
    by = rec.reduced.get("scope_s", {}).get(scope_ms.PROGRAM, {})
    dev = sum(v for k, v in by.items() if SCOPE in k)
    if not events or dev <= 0:
        return None
    least, bound = 0.0, {}
    for e in events:
        t, which = costs.least_seconds(
            *rec.arch.experts_least(e.attrs["routed"], e.attrs["used"]),
            rec.peak)
        least += t
        bound[which] = bound.get(which, 0) + 1
    rec.notes.append(f"# moe/experts: {len(events)} steps, device {dev:.6f} "
                     f"s, least {least:.6f} s, bound {bound}")
    return 100.0 * least / dev
