"""Set-up: from the harness's first statement to the first timed request
(weights, the program's graph and store, ``decide()`` and one warm-up
cold request)."""


def read(rec):
    return rec["setup_s"]
