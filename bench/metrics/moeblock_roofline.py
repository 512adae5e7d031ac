"""Kernels: the sparse-expert blocks' share of their roofline, in %: the
least time the family gives for the runs of both block executables, the
sliding-window one and the full-attention one (its ``moeblock_local`` and
``moeblock_full`` roles), over their summed device time in the trace.
Silent when the trace shows neither."""


def read(rec):
    kernels = (rec["trace"] or {}).get("kernels", {})
    found = [kernels[r] for r in ("moeblock_local", "moeblock_full")
             if r in kernels]
    if not found:
        return None
    return (100.0 * sum(k["least_s"] for k in found)
            / sum(k["device_s"] for k in found))
