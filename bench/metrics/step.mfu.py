"""Model step utilization: model FLOPs of every prefill and decode token
served in the traced window (`bench.costs`, adapters included) over the
window's length times the chip's peak FLOP/s (%)."""
from bench import costs


def read(rec):
    flops = sum(costs.prefill_flops(rec.arch, rec.adapters, i["prompt_len"])
                for _, _, _, i in rec.of("prefill"))
    flops += sum(costs.decode_step_flops(rec.arch, rec.adapters, i["kv_lens"])
                 for _, _, _, i in rec.of("decode"))
    w = rec.reduced["window_s"]
    if flops <= 0 or w <= 0:
        return None
    return 100.0 * flops / (w * rec.peak["flops_per_s"])
