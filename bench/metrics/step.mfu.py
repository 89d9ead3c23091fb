"""Model step utilization: model FLOPs of every prefill and decode token
served in the traced window (the architecture's ``prefill_flops`` and
``decode_step_flops``, adapters included) over the window's length times
the chip's peak FLOP/s (%)."""


def read(rec):
    a, ad = rec.arch, rec.adapters
    flops = sum(a.prefill_flops(ad, i["prompt_len"])
                for _, _, _, i in rec.of("prefill"))
    flops += sum(a.decode_step_flops(ad, i["kv_lens"])
                 for _, _, _, i in rec.of("decode"))
    w = rec.reduced["window_s"]
    if flops <= 0 or w <= 0:
        return None
    return 100.0 * flops / (w * rec.peak["flops_per_s"])
