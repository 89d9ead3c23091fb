"""`fused_decode_jd` (kernels/fused_decode.py): least time (the larger
of its FLOPs and bytes over the chip's peaks, the architecture's
``kernel_calls`` at the attended lengths) over the device time of its trace
events (%)."""
from bench import roofline


def read(rec):
    return roofline.share(rec, "fused_decode_jd", "jd")
