"""Whole decode: the FLOPs of the decode steps after the second token
(``flops.py``, at their positions) over the time they took on the host
clock times the chip's bf16 peak, in %."""
import flops


def read(rec):
    m, S = rec["model"], rec["mix"]["prompt_tokens"]
    work = time_s = 0.0
    for r in rec["requests"]:
        n = len(r["tokens"]) - 2
        work += sum(flops.decode_step(m, S + 1 + i)["flops"] for i in range(n))
        time_s += r["tokens"][-1] - r["tokens"][1]
    if not time_s:
        return None
    return 100.0 * work / (time_s * rec["peak"]["bf16_flops_per_s"])
