"""The comparison that decides ``correct`` for a served model."""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np


def gaps(ref_logits: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each picked token's
    reference logit lies: ``ref_logits`` (B, T, V), ``picked`` (B, T)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, np.asarray(picked)[..., None],
                             -1)[..., 0]
    return best - got


def served_rows(prompts: Sequence[np.ndarray], served: Sequence[List[int]]):
    """Token rows (B, S + N - 1) and the positions (S - 1 .. S + N - 2) at
    which each of the N served tokens was picked."""
    S = len(prompts[0])
    N = len(served[0])
    rows = np.stack([np.concatenate([p, np.asarray(s[:-1], np.int32)])
                     for p, s in zip(prompts, served)]).astype(np.int32)
    return rows, list(range(S - 1, S + N - 1))


def widest_gap(ref: Any, params: Dict[str, Any], m: Dict[str, Any],
               prompts: Sequence[np.ndarray],
               served: Sequence[List[int]]) -> float:
    rows, positions = served_rows(prompts, served)
    logits = np.asarray(ref.logits_at(params, m, rows, positions))
    return float(gaps(logits, np.asarray(served)).max())
