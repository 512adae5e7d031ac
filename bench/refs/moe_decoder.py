"""Plain float32 reference of a sparse-expert pre-norm decoder whose
layers mix sliding-window and full attention (Mellum 2).

Written from the published description and from nothing of the program:
token embedding; per layer ``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)),
Wv·n1(x))`` with grouped-query heads and a causal softmax, where a
sliding layer masks every key ``window`` or more positions behind the
query; then the expert layer: router logits ``n2(x)·Wr`` over all the
experts, a softmax, the ``top_k`` largest probabilities renormalised to
sum to one (``norm_topk_prob``), and ``x += sum p_e · Wdown_e·(silu(
Wgate_e·n2(x)) * Wup_e·n2(x))`` over the picked experts; a final RMS norm
and the output projection. RoPE rotates the two halves of each head (the
``rotate_half`` form). A sliding layer's frequencies are
``theta^(-2i/head_dim)``; a full layer's are YaRN's, as Hugging Face
``transformers`` computes them (``_compute_yarn_parameters``): with
``low``/``high`` the floor/ceiling of the correction dimensions
``d·ln(orig / (2π·beta)) / (2·ln theta)`` of ``beta_fast``/``beta_slow``,
``ramp_i = clip((i - low) / (high - low), 0, 1)`` and
``f_i = f_ext_i·(1 - ramp_i) + f_ext_i / factor·ramp_i``, cos and sin both
multiplied by ``attention_factor``.

Departures from the published model, noted as the guides ask:

* the expert share: the weights hold only the experts ``m["held"]`` of the
  ``m["experts"]`` the router spans, one chip's share of an expert-
  parallel deployment. The router still picks among all of them and
  renormalises over the picks; only picked experts that are held add
  their part, and what the others would add is left out;
* no multi-token-prediction head (the config has none) and no q/k norm
  (the config gives none);
* a norm gain ``g`` is applied as ``1 + g``, the parametrisation of the
  weights the benchmark hands to the system (``weights.py``).

Every product runs in float32 at the highest matmul precision, one layer
at a time, each layer's weights cast up from their stored bfloat16 just
before use; the output projection is taken only at the positions asked
for. ``quantize`` replaces every weight matrix (each expert's, the
router's) by its per-output-channel rounding to int8 or to fp8 first: the
controls, the reference one precision step below bfloat16.
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


def inv_freq(rope: Dict[str, Any], hd: int):
    """(hd/2,) float64 inverse frequencies and the cos/sin factor of one
    layer kind's RoPE parameters (``rope_parameters`` of the config)."""
    import numpy as np

    theta = float(rope["rope_theta"])
    ext = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rope["rope_type"] == "default":
        return ext, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    orig = rope["original_max_position_embeddings"]

    def corr(beta):
        return hd * math.log(orig / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (ext * (1 - ramp) + ext / rope["factor"] * ramp,
            float(rope["attention_factor"]))


def _rope(x, freq, factor):
    """x: (B, S, heads, hd) at positions 0..S-1."""
    import jax.numpy as jnp

    B, S, _, hd = x.shape
    half = hd // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None, :]
    cos = factor * jnp.cos(ang)[None, :, None, :]
    sin = factor * jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, m, kind):
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, S, d = x.shape
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    freq, factor = inv_freq(m["rope"][kind], hd)
    h = _rms(x, w["ln1"], m["norm_eps"])
    q = _rope((h @ w["wq"]).reshape(B, S, H, hd), freq, factor)
    k = _rope((h @ w["wk"]).reshape(B, S, KV, hd), freq, factor)
    v = (h @ w["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    behind = np.arange(S)[:, None] - np.arange(S)[None, :]
    keep = behind >= 0
    if kind == "local":
        keep &= behind < m["window"]
    s = jnp.where(jnp.asarray(keep)[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + a.reshape(B, S, H * hd) @ w["wo"]

    h = _rms(x, w["ln2"], m["norm_eps"])
    probs = jax.nn.softmax(h @ w["router"], -1)            # (B, S, experts)
    top, picked = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for j, e in enumerate(m["held"]):
        p_e = jnp.sum(jnp.where(picked == e, top, 0.0), -1)  # (B, S)
        ffn = (jax.nn.silu(h @ w["w_gate"][j]) * (h @ w["w_up"][j])
               ) @ w["w_down"][j]
        out = out + p_e[..., None] * ffn
    return x + out


def logits_at(params: Dict[str, Any], m: Dict[str, Any], tokens,
              positions: Sequence[int], *, quantize: Optional[str] = None):
    """float32 logits (B, len(positions), V) of the token rows ``tokens``
    (B, S), at ``positions`` of every row; with ``quantize`` ("int8" or
    "fp8") every weight matrix rounded first."""
    import jax
    import jax.numpy as jnp

    def up(a):
        a = jnp.asarray(a).astype(jnp.float32)
        if not quantize or a.ndim < 2:
            return a
        return jax.vmap(lambda w: _round(w, quantize))(a) if a.ndim == 3 \
            else _round(a, quantize)

    blocks = params["blocks"]
    layers = {kind: jax.jit(lambda x, w, kind=kind: _layer(x, w, m, kind))
              for kind in set(m["kinds"])}
    with jax.default_matmul_precision("highest"):
        # the embedding is a gather, not a product: never quantized
        x = jnp.asarray(params["embed"])[jnp.asarray(tokens)].astype(
            jnp.float32)
        for i, kind in enumerate(m["kinds"]):
            w = {"ln1": up(blocks["ln1"][i]), "ln2": up(blocks["ln2"][i])}
            w.update({k: up(blocks["attn"][k][i])
                      for k in ("wq", "wk", "wv", "wo")})
            w.update({k: up(blocks["moe"][k][i])
                      for k in ("router", "w_gate", "w_up", "w_down")})
            x = layers[kind](x, w)
            del w
        h = _rms(x[:, jnp.asarray(positions)], up(params["final_norm"]),
                 m["norm_eps"])
        head = (params["embed"].T if m["tied"] else params["lm_head"])
        return jax.jit(lambda h, wt: h @ wt)(h, up(head))
