"""Interval arithmetic over spans (start, end) on one clock."""
from __future__ import annotations

from typing import Iterable, List, Tuple


def merge(spans: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of ``spans`` as disjoint, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(spans: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``spans``."""
    return sum(e - s for s, e in merge(spans))


def clip(spans: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]
