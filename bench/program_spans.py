"""The program's own spans in the records of a run's requests.

``Cell.request`` (``run.py``) groups a cold start's run traces by kind, on
the host clock. A program that records its cold path's spans
(``ColdLLMResult.spans``) gives every request a ``first_token`` span; where
no request has one, the program records none of these spans, and the
readers built on this module are silent.
"""
from __future__ import annotations

from typing import Optional

from spans import clip, covered

MARK = "first_token"


def requests(rec: dict) -> list:
    return [r for r in rec["requests"] if MARK in r["spans"]]


def mean_busy(rec: dict, kind: str, within_request: bool = False
              ) -> Optional[float]:
    """Mean over the requests of the time covered by ``kind``'s spans, or
    with ``within_request`` by the part of them between the request's
    issue and its last token."""
    reqs = requests(rec)
    if not reqs:
        return None
    total = 0.0
    for r in reqs:
        got = r["spans"].get(kind, ())
        if within_request:
            got = clip(got, r["issue"], r["tokens"][-1])
        total += covered(got)
    return total / len(reqs)
