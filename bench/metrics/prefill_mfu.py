"""Whole cold start: the model FLOPs a first token needs (the family's
``prefill`` count: for a dense decoder every block over the prompt and the
output projection at its last position) over the mean time to first token
times the chip's bf16 peak, in %."""


def read(rec):
    reqs = rec["requests"]
    ttft = sum(r["first"] - r["issue"] for r in reqs) / len(reqs)
    work = rec["family"].prefill(rec["model"], rec["mix"]["prompt_tokens"])
    return 100.0 * work["flops"] / (ttft * rec["peak"]["bf16_flops_per_s"])
