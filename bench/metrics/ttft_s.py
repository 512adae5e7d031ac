"""Cold time to first token: mean over the window's requests of the time
from issue to the first token id on the host (host clock)."""


def read(rec):
    reqs = rec["requests"]
    return sum(r["first"] - r["issue"] for r in reqs) / len(reqs)
