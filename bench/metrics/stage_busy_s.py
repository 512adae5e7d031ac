"""Pipeline staging: mean over the window's requests of the time in which
at least one host-to-device ``stage`` op ran (the union of its spans, from
the program's ``RunResult.traces``)."""
from spans import covered


def read(rec):
    reqs = rec["requests"]
    return sum(covered(r["spans"].get("stage", ())) for r in reqs) / len(reqs)
