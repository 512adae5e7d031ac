"""Kernels: the tblock prefill executable's share of its roofline, in %:
the least time ``flops.py`` gives for its runs (the larger of FLOPs over
peak FLOP/s and bytes over peak bandwidth) over their summed device time
in the trace. Silent when the trace shows no such executable."""


def read(rec):
    k = (rec["trace"] or {}).get("kernels", {}).get("tblock")
    return 100.0 * k["least_s"] / k["device_s"] if k else None
