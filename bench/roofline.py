"""Roofline share of a named kernel: the least time of the window's kernel
calls (the architecture's ``kernel_calls`` at each decode step's attended
lengths and adapters) over the kernel's summed device time (%)."""
from bench import costs


def share(rec, kernel: str, mode: str):
    if rec.adapters["mode"] != mode:
        return None
    dev = rec.reduced["op_s"].get(kernel, 0.0)
    steps = rec.of("decode")
    if dev <= 0 or not steps:
        return None
    least, calls, bound = 0.0, 0, {}
    for _, _, _, info in steps:
        groups = rec.arch.kernel_calls(kernel, rec.adapters, info["kv_lens"],
                                       info["ids"])
        if groups is None:
            return None
        for fl, nb, n in groups:
            t, which = costs.least_seconds(fl, nb, rec.peak)
            least += n * t
            calls += n
            bound[which] = bound.get(which, 0) + 1
    rec.notes.append(f"# {kernel}: {calls} calls, device {dev:.6f} s, "
                     f"least {least:.6f} s, bound {bound}")
    return 100.0 * least / dev
