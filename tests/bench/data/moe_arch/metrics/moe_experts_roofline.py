"""`moe_experts`: least time of the routed experts' kernel (the
architecture's ``kernel_calls``) over the device time of its trace
events (%)."""
from bench import roofline


def read(rec):
    return roofline.share(rec, "moe_experts", "jd")
