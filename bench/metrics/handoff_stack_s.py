"""LLM bridge: mean over the window's requests of the program's
``handoff.stack`` span, the packed weights stacked into the decode pytree
and the decode server built with its KV cache (program span)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "handoff.stack")
