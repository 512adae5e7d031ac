"""How the benchmark counts the work of a call from its shapes; each model
family (``bench/families/<family>.py``) counts its own calls this way.

Counted: every matrix product (2 operations per multiply-add) and the
causal attention products (each query against its own and earlier keys
only); bytes are the bfloat16 weights read once, the activations that
enter and leave the call, and for a decode step the keys and values of the
positions it attends to. Norms, RoPE, softmax and activations are left out:
they are a few operations per element, far below the products, so the
least time below is never overstated. A count is a dict with ``flops``
and ``bytes``.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def attention_pairs(start: int, n: int) -> int:
    """(query, key) pairs when ``n`` queries at positions start..start+n-1
    attend causally."""
    return sum(start + i + 1 for i in range(n))


def least_time(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Seconds the chip needs at best: the larger of the two bounds."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
