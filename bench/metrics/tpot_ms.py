"""Time per output token once decode runs: all time from each request's
second token to its last, over all requests of the window, divided by all
tokens after the second (host clock)."""


def read(rec):
    reqs = rec["requests"]
    time_s = sum(r["tokens"][-1] - r["tokens"][1] for r in reqs)
    tokens = sum(len(r["tokens"]) - 2 for r in reqs)
    return 1000.0 * time_s / tokens if tokens else None
