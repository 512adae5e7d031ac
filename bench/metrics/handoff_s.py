"""Hand-off to decode: mean over the window's requests of the time from
the first token id on the host to the second (host clock)."""


def read(rec):
    reqs = rec["requests"]
    return sum(r["tokens"][1] - r["tokens"][0] for r in reqs) / len(reqs)
