"""Decode step's share of HBM bandwidth: the least bytes each decode step
must read (weights once, K/V of the attended tokens, the batch's adapter
factors; the architecture's ``decode_step_bytes``) over the device time of
the fused decode program (``jit__fused_decode_fn`` in the trace) times the
chip's peak bandwidth (%)."""

PROGRAM = "jit__fused_decode_fn"


def read(rec):
    steps = rec.of("decode")
    secs = rec.reduced.get("program_s", {}).get(PROGRAM, 0.0)
    if not steps or secs <= 0:
        return None
    nbytes = sum(rec.arch.decode_step_bytes(rec.adapters, i["kv_lens"],
                                            i["ids"])
                 for _, _, _, i in steps)
    return 100.0 * nbytes / (secs * rec.peak["hbm_bytes_per_s"])
