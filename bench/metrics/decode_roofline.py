"""Kernels: the decode step's share of its roofline, in %: the least time
``flops.py`` gives for its runs, each at its position, over their summed
device time in the trace. Silent when the trace shows no such
executable."""


def read(rec):
    k = (rec["trace"] or {}).get("kernels", {}).get("decode_step")
    return 100.0 * k["least_s"] / k["device_s"] if k else None
