"""Plain float32 reference of a dense pre-norm decoder (Llama / Mistral).

Written from the published description and from nothing of the program:
token embedding; per layer ``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)),
Wv·n1(x))`` with grouped-query heads and a causal softmax, then
``x += Wdown·(silu(Wgate·n2(x)) * Wup·n2(x))``; a final RMS norm and the
output projection (the transposed embedding when the two are tied). RoPE
rotates the two halves of each head (the ``rotate_half`` form), with
frequencies ``theta^(-2i/head_dim)``.

Departure from the published checkpoints, noted as the guides ask: a norm
gain ``g`` is applied as ``1 + g``, the parametrisation of the weights the
benchmark hands to the system (``weights.py``); a published gain ``w``
corresponds to ``g = w - 1``.

Every product runs in float32 at the highest matmul precision. The model
is taken one layer at a time, each layer's weights cast up from their
stored bfloat16 just before use, so that the reference fits beside the
weights on one chip; the output projection is taken only at the positions
asked for. ``quantize`` replaces every weight matrix by its per-output-
channel rounding to int8 or to fp8 first: the controls, the reference one
precision step below bfloat16.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence


def _round(w, kind: str):
    """Per-output-channel rounding of a (K, N) matrix to ``kind``
    (symmetric ``int8``, or ``fp8`` e4m3 scaled to its range), back in
    float32."""
    import jax.numpy as jnp

    top = 127.0 if kind == "int8" else 448.0
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if kind == "int8":
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1."""
    import jax.numpy as jnp

    B, S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, m):
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, w["ln1"], m["norm_eps"])
    q = _rope((h @ w["wq"]).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope((h @ w["wk"]).reshape(B, S, KV, hd), m["rope_theta"])
    v = (h @ w["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + a.reshape(B, S, H * hd) @ w["wo"]
    h = _rms(x, w["ln2"], m["norm_eps"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def logits_at(params: Dict[str, Any], m: Dict[str, Any], tokens,
              positions: Sequence[int], *, quantize: Optional[str] = None):
    """float32 logits (B, len(positions), V) of the token rows ``tokens``
    (B, S), at ``positions`` of every row; with ``quantize`` ("int8" or
    "fp8") every weight matrix rounded first."""
    import jax
    import jax.numpy as jnp

    def up(a):
        a = jnp.asarray(a).astype(jnp.float32)
        return _round(a, quantize) if quantize and a.ndim == 2 else a

    blocks = params["blocks"]
    layer = jax.jit(lambda x, w: _layer(x, w, m))
    with jax.default_matmul_precision("highest"):
        # the embedding is a gather, not a product: never quantized
        x = jnp.asarray(params["embed"])[jnp.asarray(tokens)].astype(
            jnp.float32)
        for i in range(m["layers"]):
            w = {"ln1": up(blocks["ln1"][i]), "ln2": up(blocks["ln2"][i])}
            w.update({k: up(blocks["attn"][k][i])
                      for k in ("wq", "wk", "wv", "wo")})
            w.update({k: up(blocks["mlp"][k][i])
                      for k in ("w_gate", "w_up", "w_down")})
            x = layer(x, w)
            del w
        h = _rms(x[:, jnp.asarray(positions)], up(params["final_norm"]),
                 m["norm_eps"])
        head = (params["embed"].T if m["tied"] else params["lm_head"])
        return jax.jit(lambda h, wt: h @ wt)(h, up(head))
