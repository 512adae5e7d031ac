"""Whole cold start: the model FLOPs a first token needs (every block over
the prompt, the output projection at its last position; ``flops.py``)
over the mean time to first token times the chip's bf16 peak, in %."""
import flops


def read(rec):
    reqs = rec["requests"]
    ttft = sum(r["first"] - r["issue"] for r in reqs) / len(reqs)
    work = flops.prefill(rec["model"], rec["mix"]["prompt_tokens"])
    return 100.0 * work["flops"] / (ttft * rec["peak"]["bf16_flops_per_s"])
