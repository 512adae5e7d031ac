"""Core layer library: norms, RoPE, attention (full / sliding / chunked), MLP.

All functions are pure; parameters are plain dict pytrees. Computation is done
in the config dtype (bf16 by default) with f32 softmax/norm reductions.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0e38  # large-negative float that survives bf16/f32 casts


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's inverse frequencies (head_dim/2,), as Hugging Face
    ``transformers`` computes them (``_compute_yarn_parameters``): below
    the correction dimension of ``beta_fast`` rotations the RoPE
    frequencies are kept, above that of ``beta_slow`` divided by
    ``factor``, and blended linearly in between."""
    def correction_dim(rotations):
        return (head_dim * math.log(
            yarn.original_max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extrapolated = 1.0 / theta ** (
        np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    return (extrapolated * (1.0 - ramp)
            + extrapolated / yarn.factor * ramp).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float, yarn=None,
         use_yarn=None) -> jax.Array:
    """Apply rotary embedding.

    x: (..., S, H, D) ; positions: broadcastable to (..., S). With
    ``yarn`` (a ``configs.base.Yarn``) the frequencies are YaRN's and cos
    and sin are scaled by its attention factor; ``use_yarn``, a traced
    bool, then picks YaRN or plain RoPE (a layer kind carried as data).
    """
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    scale = None
    if yarn is not None:
        yfreq = jnp.asarray(yarn_inv_freq(d, theta, yarn))
        scale = jnp.float32(yarn.attention_factor)
        if use_yarn is None:
            freq = yfreq
        else:
            freq = jnp.where(use_yarn, yfreq, freq)
            scale = jnp.where(use_yarn, scale, jnp.float32(1.0))
    angle = positions.astype(jnp.float32)[..., None] * freq  # (..., S, half)
    cos = jnp.cos(angle)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angle)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def attention_scores_mask(
    q_pos: jax.Array, k_pos: jax.Array, window: Optional[int]
) -> jax.Array:
    """Boolean mask (..., Sq, Sk): causal, optionally sliding-window."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def full_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    q_pos: jax.Array,  # (B, S)
    k_pos: jax.Array,  # (B, S)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Materialized masked attention — used for short sequences (training)."""
    B, S, H, D = q.shape
    kv = k.shape[2]
    k = _repeat_kv(k, H // kv)
    v = _repeat_kv(v, H // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    scores = _softcap(scores, softcap)
    mask = attention_scores_mask(q_pos, k_pos, window)[:, None]  # (B,1,Sq,Sk)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention scanned over key chunks.

    Keeps live memory O(S * chunk) instead of O(S^2) — this is the pure-jnp
    flash-attention analogue used for 32k prefill. Numerically identical to
    ``full_attention`` (same f32 softmax).
    """
    B, S, H, D = q.shape
    kv_heads = k.shape[2]
    Sk = k.shape[1]
    assert Sk % chunk == 0, (Sk, chunk)
    n_chunks = Sk // chunk
    k = k.reshape(B, n_chunks, chunk, kv_heads, D).transpose(1, 0, 2, 3, 4)
    v = v.reshape(B, n_chunks, chunk, kv_heads, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    scale = 1.0 / math.sqrt(D)

    def body(carry, xs):
        # named_scope marks this traffic as VMEM-resident under the Pallas
        # flash kernel (repro.kernels.attention) — the roofline's modeled-
        # kernel iteration classifies HLO ops by this scope (§Perf B).
        with jax.named_scope("flashable_attn"):
            m, l, acc = carry  # (B,H,S), (B,H,S), (B,S,H,D)
            kc, vc, kpc = xs
            kc = _repeat_kv(kc, H // kv_heads)
            vc = _repeat_kv(vc, H // kv_heads)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            mask = attention_scores_mask(q_pos, kpc, window)[:, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(q.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k, v, kp))
    out = acc / jnp.maximum(l, 1e-37).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------
def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def attn_init(key, cfg) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H * hd), dt),
        "wk": _dense_init(ks[1], (d, KV * hd), dt),
        "wv": _dense_init(ks[2], (d, KV * hd), dt),
        "wo": _dense_init(ks[3], (H * hd, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def mlp_init(key, cfg, d_ff=None) -> dict:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, ff), dt),
        "w_up": _dense_init(ks[1], (d, ff), dt),
        "w_down": _dense_init(ks[2], (ff, d), dt),
    }


def mlp_apply(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# attention apply (sequence mode: train / prefill)
# ---------------------------------------------------------------------------
def attn_qkv(p: dict, x: jax.Array, cfg, positions: jax.Array, *,
             yarn=None, use_yarn=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta, yarn, use_yarn)
    k = rope(k, positions, cfg.rope_theta, yarn, use_yarn)
    return q, k, v


def attn_apply_seq(
    p: dict,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    chunked: bool = False,
    chunk: int = 1024,
    yarn=None,
    use_yarn=None,
):
    """Self-attention over a full sequence. Returns (out, (k, v)) so callers
    can keep the KV for cache initialisation (prefill). ``window`` may be
    traced (a layer kind carried as data); ``yarn``/``use_yarn`` as in
    ``rope``."""
    B, S, _ = x.shape
    q, k, v = attn_qkv(p, x, cfg, positions, yarn=yarn, use_yarn=use_yarn)

    out = _maybe_seqpar_attention(q, k, v, positions, cfg, window, chunked, chunk)
    if out is None:
        fn = chunked_attention if chunked else full_attention
        kwargs = dict(window=window, softcap=cfg.attn_softcap)
        if chunked:
            kwargs["chunk"] = chunk
        out = fn(q, k, v, positions, positions, **kwargs)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, (k, v)


def _maybe_seqpar_attention(q, k, v, positions, cfg, window, chunked, chunk):
    """Sequence-parallel attention (runtime flag `seqpar_attn`): shard the
    query sequence over `model` when the head count can't be — K/V stay full
    per shard (GQA keeps them small). Returns None when not applicable."""
    from repro.models.runtime_flags import FLAGS

    if not FLAGS.get("seqpar_attn", False):
        return None
    mesh = _mesh_ctx()
    if mesh is None:
        return None
    names = dict(mesh.shape)
    msize = names.get("model", 1)
    B, S, H, hd = q.shape
    if msize <= 1 or H % msize == 0 or S % msize != 0:
        return None  # heads shard fine (or seq can't) — use baseline TP
    if chunked and (S // msize) % chunk != 0:
        chunk = max(128, (S // msize) // 4)
    db = tuple(a for a in ("pod", "data") if a in names)
    import math as _math

    dsize = _math.prod(names[a] for a in db) if db else 1
    bax = db if db and B % dsize == 0 and dsize > 1 else None

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(q_loc, k_full, v_full, qpos_loc, kpos_full):
        fn = chunked_attention if chunked else full_attention
        kwargs = dict(window=window, softcap=cfg.attn_softcap)
        if chunked:
            kwargs["chunk"] = chunk
        return fn(q_loc, k_full, v_full, qpos_loc, kpos_full, **kwargs)

    return shard_map(
        body, mesh=mesh,
        in_specs=(
            P(bax, "model", None, None),
            P(bax, None, None, None),
            P(bax, None, None, None),
            P(bax, "model"),
            P(bax, None),
        ),
        out_specs=P(bax, "model", None, None),
        check_vma=False,
    )(q, k, v, positions, positions)


# ---------------------------------------------------------------------------
# attention decode step with ring-buffer KV cache
# ---------------------------------------------------------------------------
def _mesh_ctx():
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def _decode_shard_axes(B: int, W: int, mesh):
    """(batch_axes, seq_axes) mirroring sharding.decode_state_specs."""
    import math as _math

    names = dict(mesh.shape)
    db = tuple(a for a in ("pod", "data") if a in names)
    dsize = _math.prod(names[a] for a in db) if db else 1
    msize = names.get("model", 1)
    if db and B % dsize == 0 and dsize > 1:
        if msize > 1 and W % msize == 0:
            return db, ("model",)
        return db, None
    seqs = tuple(a for a in (*db, "model") if names.get(a, 1) > 1)
    if seqs and W % _math.prod(names[a] for a in seqs) == 0:
        return None, seqs
    return None, None


def _flash_decode_sharded(
    q: jax.Array,        # (B, H, hd)
    cache_k: jax.Array,  # (B, W, KV, hd)
    cache_v: jax.Array,
    pos: jax.Array,
    W: int,
    *,
    window: Optional[int],
    softcap: Optional[float],
    mesh,
    batch_axes,
    seq_axes,
    k_scale=None,
    v_scale=None,
):
    """Flash-decoding over a sequence-sharded cache: each seq shard computes
    a partial (m, l, acc), combined with pmax/psum over the seq axes — the
    wire cost per layer is O(B·H·hd), not O(B·W·KV·hd). Supports the int8
    cache (per-entry scales dequantized in-shard)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, H, hd = q.shape
    KV = cache_k.shape[2]
    names = dict(mesh.shape)
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    def body(q, kc, vc, pos, ks, vs):
        Wl = kc.shape[1]
        idx = jnp.int32(0)
        for ax in seq_axes or ():
            idx = idx * names[ax] + jax.lax.axis_index(ax)
        slots = idx * Wl + jnp.arange(Wl, dtype=jnp.int32)
        entry_pos = pos - jnp.mod(pos - slots, W)
        valid = entry_pos >= 0
        if window is not None:
            valid &= entry_pos > pos - window
        if quant:
            kc = kc.astype(q.dtype) * ks[..., None].astype(q.dtype)
            vc = vc.astype(q.dtype) * vs[..., None].astype(q.dtype)
        kk = _repeat_kv(kc, H // KV)
        vv = _repeat_kv(vc, H // KV)
        # preferred_element_type keeps the dot's operands bf16 (mixed-
        # precision HLO dot) — an explicit .astype(f32) on the operands would
        # make XLA carry the whole cache in f32 across the layer loop
        s = jnp.einsum("bhd,bkhd->bhk", q, kk,
                       preferred_element_type=jnp.float32) * scale
        s = _softcap(s, softcap)
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        m = s.max(axis=-1)                                    # (B, H)
        p = jnp.exp(s - m[..., None]) * valid[None, None, :]
        l = p.sum(axis=-1)
        acc = jnp.einsum("bhk,bkhd->bhd", p.astype(vv.dtype), vv).astype(jnp.float32)
        if seq_axes:
            mg = jax.lax.pmax(m, seq_axes)
            corr = jnp.exp(m - mg)
            l = jax.lax.psum(l * corr, seq_axes)
            acc = jax.lax.psum(acc * corr[..., None], seq_axes)
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        return out.astype(q.dtype)

    bax = batch_axes if batch_axes else None
    if not quant:
        # dummy scalar placeholders keep one body signature
        k_scale = jnp.zeros((), jnp.float32)
        v_scale = jnp.zeros((), jnp.float32)
        scale_spec = P()
    else:
        scale_spec = P(bax, seq_axes, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(
            P(bax, None, None),
            P(bax, seq_axes, None, None),
            P(bax, seq_axes, None, None),
            P(),
            scale_spec,
            scale_spec,
        ),
        out_specs=P(bax, None, None),
        check_vma=False,
    )(q, cache_k, cache_v, pos, k_scale, v_scale)



def _quantize_kv(k: jax.Array):
    """(B, 1, KV, hd) -> (int8 values, f32 scale (B,1,KV))."""
    amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def attn_decode_step(
    p: dict,
    x: jax.Array,        # (B, 1, d)
    cache_k: jax.Array,  # (B, W, KV, hd)  bf16, or int8 when quantized
    cache_v: jax.Array,
    pos: jax.Array,      # scalar int32 — position of the new token
    cfg,
    *,
    window: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # (B, W, KV) f32 when int8 cache
    v_scale: Optional[jax.Array] = None,
    yarn=None,
    use_yarn=None,
):
    """One decode step. The cache is a ring buffer of length W; for full
    attention W == max_len and no entry is ever overwritten. ``window``
    may be traced; ``yarn``/``use_yarn`` as in ``rope``. Returns
    (out, (cache_k, cache_v[, k_scale, v_scale]))."""
    B, _, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = cache_k.shape[1]
    quant = k_scale is not None
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, KV, hd)
    v = (x @ p["wv"]).reshape(B, 1, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q = rope(q, posb, cfg.rope_theta, yarn, use_yarn)
    k = rope(k, posb, cfg.rope_theta, yarn, use_yarn)
    slot = jnp.mod(pos, W)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        cache_k = jax.lax.dynamic_update_slice(cache_k, kq, (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, vq, (0, slot, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(k_scale, ks, (0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(v_scale, vs, (0, slot, 0))
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))

    # --- sharded read path: flash-decoding over a seq-sharded cache --------
    from repro.models.runtime_flags import FLAGS

    mesh = _mesh_ctx() if FLAGS.get("decode_flash", True) else None
    if mesh is not None:
        bax, sax = _decode_shard_axes(B, W, mesh)
        if sax is not None:
            out = _flash_decode_sharded(
                q[:, 0], cache_k, cache_v, pos, W,
                window=window, softcap=cfg.attn_softcap,
                mesh=mesh, batch_axes=bax, seq_axes=sax,
                k_scale=k_scale, v_scale=v_scale,
            )
            out = out.reshape(B, 1, H * hd) @ p["wo"]
            caches = ((cache_k, cache_v, k_scale, v_scale) if quant
                      else (cache_k, cache_v))
            return out, caches

    # --- unsharded / XLA-auto read path -------------------------------------
    # reconstruct absolute position of each slot
    slots = jnp.arange(W, dtype=jnp.int32)
    entry_pos = pos - jnp.mod(pos - slots, W)   # in (pos-W, pos]
    valid = entry_pos >= 0
    if window is not None:
        valid &= entry_pos > pos - window
    if quant:
        kk = _repeat_kv(cache_k.astype(x.dtype)
                        * k_scale[..., None].astype(x.dtype), H // KV)
        vv = _repeat_kv(cache_v.astype(x.dtype)
                        * v_scale[..., None].astype(x.dtype), H // KV)
    else:
        kk = _repeat_kv(cache_k, H // KV)
        vv = _repeat_kv(cache_v, H // KV)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = _softcap(scores, cfg.attn_softcap)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    caches = ((cache_k, cache_v, k_scale, v_scale) if quant
              else (cache_k, cache_v))
    return out, caches
