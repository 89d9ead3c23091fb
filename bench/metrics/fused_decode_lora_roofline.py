"""`fused_decode_lora` (kernels/fused_decode.py): least time (the larger
of its FLOPs and bytes over the chip's peaks, at the attended lengths and
the batch's distinct adapters) over the device time of its trace events
(%)."""
from bench import roofline


def read(rec):
    return roofline.share(rec, "fused_decode_lora", "lora")
