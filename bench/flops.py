"""Operations and bytes that a dense decoder's calls need, from its shapes.

Counted: every matrix product (2 operations per multiply-add) and the
causal attention products (each query against its own and earlier keys
only); bytes are the bfloat16 weights read once, the activations that
enter and leave the call, and for a decode step the keys and values of the
positions it attends to. Norms, RoPE, softmax and activations are left out:
they are a few operations per element, far below the products, so the
least time below is never overstated. ``m`` is a ``configs.model`` dict.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def _block_weights(m: Dict) -> int:
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def _attention_pairs(start: int, n: int) -> int:
    """(query, key) pairs when ``n`` queries at positions start..start+n-1
    attend causally."""
    return sum(start + i + 1 for i in range(n))


def tblock(m: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One decoder block over a whole prompt (the cold prefill's unit)."""
    H, hd, d = m["heads"], m["head_dim"], m["d_model"]
    flops = (2 * batch * seq * _block_weights(m)
             + 2 * 2 * batch * H * hd * _attention_pairs(0, seq))
    nbytes = BF16 * (_block_weights(m) + 2 * d) + 2 * BF16 * batch * seq * d
    return {"flops": float(flops), "bytes": float(nbytes)}


def lm_head(m: Dict, positions: int = 1) -> Dict[str, float]:
    d, V = m["d_model"], m["vocab"]
    return {"flops": float(2 * positions * d * V),
            "bytes": float(BF16 * (d * V + d) + 4 * positions * V)}


def decode_step(m: Dict, pos: int, batch: int = 1) -> Dict[str, float]:
    """One token through every layer and the output projection, attending
    to positions 0..pos."""
    L, H, KV, hd, d = (m["layers"], m["heads"], m["kv_heads"],
                       m["head_dim"], m["d_model"])
    head = lm_head(m, batch)
    flops = (L * (2 * batch * _block_weights(m)
                  + 2 * 2 * batch * H * hd * (pos + 1))
             + head["flops"])
    nbytes = (L * (BF16 * (_block_weights(m) + 2 * d)
                   + 2 * BF16 * batch * (pos + 1) * KV * hd)
              + head["bytes"] + BF16 * batch * d)
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill(m: Dict, seq: int) -> Dict[str, float]:
    """The model work that a first token needs: every block over the
    prompt, and the output projection at the last position."""
    blk, head = tblock(m, seq), lm_head(m, 1)
    return {"flops": m["layers"] * blk["flops"] + head["flops"],
            "bytes": m["layers"] * blk["bytes"] + head["bytes"]}


def least_time(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Seconds the chip needs at best: the larger of the two bounds."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def decode_least_time(m: Dict, positions: Iterable[int],
                      peak: Dict[str, float]) -> float:
    return sum(least_time(decode_step(m, p), peak) for p in positions)
