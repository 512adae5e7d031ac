"""LLM bridge: mean over the window's requests of the program's
``handoff.copy`` span, the host copy of the packed decode weights that
``register_packed_state`` takes (program span)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "handoff.copy")
