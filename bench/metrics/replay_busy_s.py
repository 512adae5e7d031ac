"""LLM bridge: mean over the window's requests of the program's
``handoff.replay`` span, the decode server's admission, which replays
the prompt and the first token into the KV cache and picks the second
token; it holds no decode tick (program span; the program's own
counterpart of ``replay_s``)."""
import program_spans


def read(rec):
    return program_spans.mean_busy(rec, "handoff.replay")
