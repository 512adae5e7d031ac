"""LLM bridge: mean over the window's requests of the time from the decode
server's KV reservation to the second token id on the host: the prompt
replayed through ``decode_step`` (host clock)."""


def read(rec):
    reqs = [r for r in rec["requests"] if r["decode_start"] is not None]
    if not reqs:
        return None
    return sum(r["tokens"][1] - r["decode_start"] for r in reqs) / len(reqs)
