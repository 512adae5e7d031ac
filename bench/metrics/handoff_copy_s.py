"""LLM bridge: mean over the window's requests of the program's
``handoff.copy`` span, the registration of the packed decode weights for
warm-state transfer by ``register_packed_state``; the weights stay on the
device (program span)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "handoff.copy")
