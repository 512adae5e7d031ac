"""Kernels: the sparse-expert decode step's share of its roofline, in %:
the least time the family gives for its runs, each at its position and
at the expected routing (its ``moe_decode_step`` role), over their summed
device time in the trace. Silent when the trace shows no such
executable."""


def read(rec):
    k = (rec["trace"] or {}).get("kernels", {}).get("moe_decode_step")
    return 100.0 * k["least_s"] / k["device_s"] if k else None
