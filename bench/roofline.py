"""Roofline share of a fused decode kernel: the least time of the window's
kernel calls over the kernel's summed device time (%)."""
from bench import costs


def share(rec, kernel: str, mode: str):
    if rec.adapters["mode"] != mode:
        return None
    dev = rec.reduced["kernel_s"].get(kernel, 0.0)
    steps = rec.of("decode")
    if dev <= 0 or not steps:
        return None
    least, bound = 0.0, {}
    for _, _, _, info in steps:
        fl, nb = costs.fused_decode_call(rec.arch, rec.adapters,
                                         info["kv_lens"], info["ids"])
        t, which = costs.least_seconds(fl, nb, rec.peak)
        least += rec.arch.L * t
        bound[which] = bound.get(which, 0) + 1
    rec.notes.append(f"# {kernel}: {len(steps) * rec.arch.L} calls, device "
                     f"{dev:.6f} s, least {least:.6f} s, bound {bound}")
    return 100.0 * least / dev
