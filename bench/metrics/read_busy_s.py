"""I/O layer: mean over the window's requests of the time in which at
least one ``read`` op of the cold pipeline ran (the union of its spans,
from the program's ``RunResult.traces``)."""
from spans import covered


def read(rec):
    reqs = rec["requests"]
    return sum(covered(r["spans"].get("read", ())) for r in reqs) / len(reqs)
