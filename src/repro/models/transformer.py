"""Config-driven decoder covering all assigned families.

One ``init_params`` / ``forward`` / ``prefill`` / ``decode_step`` implements
dense, moe, ssm (Mamba2), hybrid (Zamba2), vlm and audio architectures, driven
entirely by ``ArchConfig``. Layers are stacked and scanned with
``jax.lax.scan`` so the lowered HLO is O(1) in depth — essential for the
40-pair × 2-mesh multi-pod dry-run.

Layer layout per family:
  dense/moe/vlm/audio : blocks stacked (L, ...); gemma2 scans (L/2, 2, ...)
                        pairs of (local-window, global) layers; a config
                        with a ``layer_pattern`` scans each layer's kind
                        as data beside its weights.
  ssm                 : mamba blocks stacked (L, ...).
  hybrid (zamba2)     : mamba blocks scanned in groups of
                        ``shared_attn_every``; one *shared* attention+mlp
                        block (single copy of weights) applied between groups.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

Params = Dict[str, Any]

CHUNKED_ATTN_THRESHOLD = 8192  # prefill longer than this uses online-softmax
ATTN_CHUNK = 1024
# the window of a full-attention layer whose kind is traced data: wider
# than any position, so it masks nothing
NO_WINDOW = 2**30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _attn_block_init(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "attn": L.attn_init(k1, cfg),
    }
    if cfg.is_moe:
        p["moe"] = MOE.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def _mamba_block_init(key, cfg: ArchConfig) -> Params:
    return {
        "ln1": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.dtype)),
        "mamba": SSM.mamba_init(key, cfg),
    }


def init_params(key, cfg: ArchConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    d, V = cfg.d_model, cfg.vocab_size
    kemb, khead, kblocks, kshared = jax.random.split(key, 4)
    params: Params = {}
    if cfg.input_mode in ("tokens", "vlm"):
        params["embed"] = (jax.random.normal(kemb, (V, d), jnp.float32) * 0.02).astype(dt)
    if not cfg.tie_embeddings or cfg.input_mode == "embeddings":
        params["lm_head"] = (
            jax.random.normal(khead, (d, V), jnp.float32) / math.sqrt(d)
        ).astype(dt)

    lkeys = jax.random.split(kblocks, cfg.num_layers)
    if cfg.family in ("ssm", "hybrid"):
        params["blocks"] = jax.vmap(lambda k: _mamba_block_init(k, cfg))(lkeys)
    else:
        params["blocks"] = jax.vmap(lambda k: _attn_block_init(k, cfg))(lkeys)
    if cfg.family == "hybrid":
        params["shared"] = _attn_block_init(kshared, cfg)
    params["final_norm"] = jnp.zeros((d,), dt)
    return params


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------
def _kind_flags(cfg: ArchConfig) -> jax.Array:
    """(L,) bool, True where a layer of ``cfg.layer_pattern`` is local: the
    layer kinds scanned as data through the stacked blocks."""
    return jnp.asarray([k == "local" for k in cfg.layer_kinds()])


def _kind_window(cfg: ArchConfig, local: jax.Array) -> jax.Array:
    """The attention window of a layer whose kind is the traced ``local``."""
    return jnp.where(local, cfg.sliding_window or NO_WINDOW, NO_WINDOW)


def _rope_kw(cfg: ArchConfig, local) -> dict:
    """YaRN on the full layers: on every layer where kinds are not data."""
    return {"yarn": cfg.rope_yarn,
            "use_yarn": None if local is None else jnp.logical_not(local)}


def _attn_block_seq(bp, x, cfg, positions, window, chunked, collect_kv,
                    local=None):
    from repro.models.runtime_flags import FLAGS

    h, kv = L.attn_apply_seq(
        bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), cfg, positions,
        window=window, chunked=chunked,
        chunk=int(FLAGS.get("attn_chunk", ATTN_CHUNK)),
        **_rope_kw(cfg, local),
    )
    x = x + h
    xn = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if "moe" in bp:
        h2, aux = MOE.moe_apply(bp["moe"], xn, cfg)
    else:
        h2, aux = L.mlp_apply(bp["mlp"], xn), jnp.zeros((), jnp.float32)
    return x + h2, aux, (kv if collect_kv else None)


def _mamba_block_seq(bp, x, cfg, conv_states=None, ssm_state=None):
    h, states = SSM.mamba_apply_seq(
        bp["mamba"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), cfg,
        conv_states=conv_states, ssm_state=ssm_state,
    )
    return x + h, states


def _embed_input(params, cfg, batch) -> tuple[jax.Array, jax.Array]:
    """Returns (x (B,S,d), loss_mask (B,S))."""
    if cfg.input_mode == "tokens":
        x = params["embed"][batch["tokens"]]
        mask = jnp.ones(batch["tokens"].shape, jnp.float32)
    elif cfg.input_mode == "embeddings":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        mask = jnp.ones(x.shape[:2], jnp.float32)
    elif cfg.input_mode == "vlm":
        tok = params["embed"][batch["tokens"]]
        pre = batch["prefix_embeds"].astype(jnp.dtype(cfg.dtype))
        x = jnp.concatenate([pre, tok], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros(pre.shape[:2], jnp.float32),
             jnp.ones(tok.shape[:2], jnp.float32)], axis=1,
        )
    else:
        raise ValueError(cfg.input_mode)
    from repro.models.sharding import constrain_batch
    return constrain_batch(x), mask


def _lm_logits(params, cfg, x) -> jax.Array:
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and cfg.input_mode != "embeddings":
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


# ---------------------------------------------------------------------------
# sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ArchConfig,
    *,
    remat: bool = False,
    remat_group: int = 1,
    collect_cache: bool = False,
):
    """Full-sequence forward. Returns (logits, aux_loss, cache_or_None).

    cache (when collect_cache): family-specific pytree usable to seed
    ``decode_step`` at position S.
    """
    x, loss_mask = _embed_input(params, cfg, batch)
    B, S, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    chunked = S > CHUNKED_ATTN_THRESHOLD
    aux_total = jnp.zeros((), jnp.float32)
    cache = None
    blocks = params["blocks"]

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        window = cfg.sliding_window

        if cfg.local_global_pattern:
            def body(carry, bp2):
                x = carry
                bpl = jax.tree.map(lambda a: a[0], bp2)
                bpg = jax.tree.map(lambda a: a[1], bp2)
                x, a1, kv1 = _attn_block_seq(bpl, x, cfg, positions, window, chunked, collect_cache)
                x, a2, kv2 = _attn_block_seq(bpg, x, cfg, positions, None, chunked, collect_cache)
                return x, (a1 + a2, (kv1, kv2))
            blocks2 = jax.tree.map(
                lambda a: a.reshape(cfg.num_layers // 2, 2, *a.shape[1:]), blocks
            )
            if remat:
                body = jax.checkpoint(body)
            x, (auxs, kvs) = jax.lax.scan(body, x, blocks2)
            aux_total = auxs.sum()
            if collect_cache:
                cache = {"local": kvs[0], "global": kvs[1]}
        elif cfg.layer_pattern:
            def body(x, xs):
                bp, loc = xs
                x, a, kv = _attn_block_seq(bp, x, cfg, positions,
                                           _kind_window(cfg, loc), chunked,
                                           collect_cache, local=loc)
                return x, (a, kv)
            if remat:
                body = jax.checkpoint(body)
            x, (auxs, kvs) = jax.lax.scan(body, x, (blocks, _kind_flags(cfg)))
            aux_total = auxs.sum()
            if collect_cache:
                cache = {"kv": kvs}
        else:
            def body(x, bp):
                x, a, kv = _attn_block_seq(bp, x, cfg, positions, window, chunked, collect_cache)
                return x, (a, kv)
            g = remat_group if (remat and cfg.num_layers % max(remat_group, 1) == 0) else 1
            if g > 1:
                # hierarchical remat: checkpoint GROUPS of g layers so the
                # saved residual stack is L/g deep (trades one extra forward
                # of the inner layers for g× less activation memory)
                def gbody(x, gbp):
                    def inner(x, bp):
                        x, a, kv = _attn_block_seq(bp, x, cfg, positions, window, chunked, collect_cache)
                        return x, (a, kv)
                    return jax.lax.scan(inner, x, gbp)
                gbody = jax.checkpoint(gbody)
                gblocks = jax.tree.map(
                    lambda a: a.reshape(cfg.num_layers // g, g, *a.shape[1:]),
                    blocks)
                x, (auxs, kvs) = jax.lax.scan(gbody, x, gblocks)
                if collect_cache and kvs is not None:
                    kvs = jax.tree.map(
                        lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), kvs)
            else:
                if remat:
                    body = jax.checkpoint(body)
                x, (auxs, kvs) = jax.lax.scan(body, x, blocks)
            aux_total = auxs.sum()
            if collect_cache:
                cache = {"kv": kvs}

    elif cfg.family == "ssm":
        def body(x, bp):
            x, states = _mamba_block_seq(bp, x, cfg)
            return x, (states if collect_cache else None)
        if remat:
            body = jax.checkpoint(body)
        x, states = jax.lax.scan(body, x, blocks)
        if collect_cache:
            cache = {"mamba": states}

    elif cfg.family == "hybrid":
        every = cfg.shared_attn_every
        G = cfg.num_layers // every
        shared = params["shared"]

        def group(carry, gbp):
            x = carry
            def inner(x, bp):
                x, states = _mamba_block_seq(bp, x, cfg)
                return x, (states if collect_cache else None)
            x, mstates = jax.lax.scan(inner, x, gbp)
            x, a, kv = _attn_block_seq(shared, x, cfg, positions, cfg.sliding_window, chunked, collect_cache)
            return x, (a, mstates, kv)
        gblocks = jax.tree.map(lambda a: a.reshape(G, every, *a.shape[1:]), blocks)
        if remat:
            group = jax.checkpoint(group)
        x, (auxs, mstates, kvs) = jax.lax.scan(group, x, gblocks)
        aux_total = auxs.sum()
        if collect_cache:
            cache = {"mamba": mstates, "shared_kv": kvs}
    else:
        raise ValueError(cfg.family)

    logits = _lm_logits(params, cfg, x)
    return logits, aux_total, (cache, loss_mask) if collect_cache else (None, loss_mask)


def loss_fn(params, batch, cfg: ArchConfig, *, remat: bool = False,
            remat_group: int = 1):
    """Next-token cross-entropy. Returns (loss, metrics)."""
    logits, aux, (_, mask) = forward(params, batch, cfg, remat=remat,
                                     remat_group=remat_group)
    if cfg.input_mode == "vlm":
        labels = batch["tokens"]
        P = cfg.num_prefix_embeds
        logits_text = logits[:, P:, :]
        lg = logits_text[:, :-1]
        lb = labels[:, 1:]
        m = mask[:, P + 1:]
    elif cfg.input_mode == "embeddings":
        lg = logits[:, :-1]
        lb = batch["labels"][:, 1:]
        m = mask[:, 1:]
    else:
        lg = logits[:, :-1]
        lb = batch["tokens"][:, 1:]
        m = mask[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    # label log-prob via a masked reduction over the vocab dim: unlike
    # take_along_axis (a gather), this stays partitionable when the vocab
    # dim is sharded over the model axis — a gather would force SPMD to
    # replicate the full logits tensor on every device.
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, len(lg.shape) - 1)
    ll = jnp.sum(jnp.where(vocab_iota == lb[..., None], lg, 0.0), axis=-1)
    nll = (logz - ll) * m
    loss = nll.sum() / jnp.maximum(m.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ArchConfig, batch: int, context_len: int) -> Params:
    """Zero-initialised decode caches sized for ``context_len`` history."""
    dt = jnp.dtype(cfg.dtype)
    KV, hd, Lr = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers

    def kv(n, W):
        return jnp.zeros((n, batch, W, KV, hd), dt)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        if cfg.local_global_pattern:
            Wl = min(cfg.sliding_window, context_len)
            return {
                "k_local": kv(Lr // 2, Wl), "v_local": kv(Lr // 2, Wl),
                "k_global": kv(Lr // 2, context_len), "v_global": kv(Lr // 2, context_len),
            }
        if cfg.layer_pattern:
            # kinds mixed in one stack: every layer keeps the full length
            return {"k": kv(Lr, context_len), "v": kv(Lr, context_len)}
        W = min(cfg.sliding_window, context_len) if cfg.sliding_window else context_len
        from repro.models.runtime_flags import FLAGS
        if FLAGS.get("kv_cache_int8", False):
            return {
                "k": jnp.zeros((Lr, batch, W, KV, hd), jnp.int8),
                "v": jnp.zeros((Lr, batch, W, KV, hd), jnp.int8),
                "k_scale": jnp.zeros((Lr, batch, W, KV), jnp.float32),
                "v_scale": jnp.zeros((Lr, batch, W, KV), jnp.float32),
            }
        return {"k": kv(Lr, W), "v": kv(Lr, W)}
    if cfg.family == "ssm":
        s = SSM.mamba_state_init(cfg, batch, dt)
        return {k: jnp.zeros((Lr, *v.shape), v.dtype) for k, v in s.items()}
    if cfg.family == "hybrid":
        G = cfg.num_layers // cfg.shared_attn_every
        s = SSM.mamba_state_init(cfg, batch, dt)
        mamba = {k: jnp.zeros((G, cfg.shared_attn_every, *v.shape), v.dtype) for k, v in s.items()}
        W = min(cfg.sliding_window, context_len) if cfg.sliding_window else context_len
        mamba["shared_k"] = kv(G, W)
        mamba["shared_v"] = kv(G, W)
        return mamba
    raise ValueError(cfg.family)


def _attn_block_decode(bp, x, ck, cv, pos, cfg, window, local=None):
    h, (ck, cv) = L.attn_decode_step(
        bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), ck, cv, pos, cfg,
        window=window, **_rope_kw(cfg, local),
    )
    x = x + h
    xn = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if "moe" in bp:
        h2, _ = MOE.moe_apply(bp["moe"], xn, cfg)
    else:
        h2 = L.mlp_apply(bp["mlp"], xn)
    return x + h2, ck, cv


def _mamba_block_decode(bp, x, st, cfg):
    h, ((sx, sB, sC), ssm) = SSM.mamba_decode_step(
        bp["mamba"], L.rms_norm(x, bp["ln1"], cfg.norm_eps), cfg,
        (st["conv_x"], st["conv_B"], st["conv_C"]), st["ssm"],
    )
    return x + h, {"conv_x": sx, "conv_B": sB, "conv_C": sC, "ssm": ssm}


def decode_step(
    params: Params,
    state: Params,
    batch: Dict[str, jax.Array],
    pos: jax.Array,  # scalar int32: position of the incoming token
    cfg: ArchConfig,
):
    """One token decode for a batch. Returns (logits (B,1,V), new_state)."""
    if cfg.input_mode == "embeddings":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = params["embed"][batch["tokens"]]
    from repro.models.sharding import constrain_batch
    x = constrain_batch(x)
    blocks = params["blocks"]

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        if cfg.local_global_pattern:
            def body(x, xs):
                bp2, kl, vl, kg, vg = xs
                bpl = jax.tree.map(lambda a: a[0], bp2)
                bpg = jax.tree.map(lambda a: a[1], bp2)
                x, kl, vl = _attn_block_decode(bpl, x, kl, vl, pos, cfg, cfg.sliding_window)
                x, kg, vg = _attn_block_decode(bpg, x, kg, vg, pos, cfg, None)
                return x, (kl, vl, kg, vg)
            blocks2 = jax.tree.map(
                lambda a: a.reshape(cfg.num_layers // 2, 2, *a.shape[1:]), blocks
            )
            x, (kl, vl, kg, vg) = jax.lax.scan(
                body, x, (blocks2, state["k_local"], state["v_local"],
                          state["k_global"], state["v_global"]))
            state = {"k_local": kl, "v_local": vl, "k_global": kg, "v_global": vg}
        elif cfg.layer_pattern:
            def body(x, xs):
                bp, ck, cv, loc = xs
                x, ck, cv = _attn_block_decode(bp, x, ck, cv, pos, cfg,
                                               _kind_window(cfg, loc), loc)
                return x, (ck, cv)
            x, (ck, cv) = jax.lax.scan(
                body, x, (blocks, state["k"], state["v"], _kind_flags(cfg)))
            state = {"k": ck, "v": cv}
        else:
            window = cfg.sliding_window
            quant = "k_scale" in state

            if quant:
                def body(x, xs):
                    bp, ck, cv, ks, vs = xs
                    h, (ck, cv, ks, vs) = L.attn_decode_step(
                        bp["attn"], L.rms_norm(x, bp["ln1"], cfg.norm_eps),
                        ck, cv, pos, cfg, window=window,
                        k_scale=ks, v_scale=vs)
                    x = x + h
                    xn = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
                    if "moe" in bp:
                        h2, _ = MOE.moe_apply(bp["moe"], xn, cfg)
                    else:
                        h2 = L.mlp_apply(bp["mlp"], xn)
                    return x + h2, (ck, cv, ks, vs)
                x, (ck, cv, ks, vs) = jax.lax.scan(
                    body, x, (blocks, state["k"], state["v"],
                              state["k_scale"], state["v_scale"]))
                state = {"k": ck, "v": cv, "k_scale": ks, "v_scale": vs}
            else:
                def body(x, xs):
                    bp, ck, cv = xs
                    x, ck, cv = _attn_block_decode(bp, x, ck, cv, pos, cfg, window)
                    return x, (ck, cv)
                x, (ck, cv) = jax.lax.scan(body, x, (blocks, state["k"], state["v"]))
                state = {"k": ck, "v": cv}

    elif cfg.family == "ssm":
        def body(x, xs):
            bp, st = xs
            x, st = _mamba_block_decode(bp, x, cfg=cfg, st=st)
            return x, st
        mamba_state = {k: state[k] for k in ("conv_x", "conv_B", "conv_C", "ssm")}
        x, new_state = jax.lax.scan(body, x, (blocks, mamba_state))
        state = new_state

    elif cfg.family == "hybrid":
        shared = params["shared"]
        every = cfg.shared_attn_every
        G = cfg.num_layers // every

        def group(x, xs):
            gbp, mst, sk, sv = xs
            def inner(x, ys):
                bp, st = ys
                x, st = _mamba_block_decode(bp, x, cfg=cfg, st=st)
                return x, st
            x, mst = jax.lax.scan(inner, x, (gbp, mst))
            x, sk, sv = _attn_block_decode(shared, x, sk, sv, pos, cfg, cfg.sliding_window)
            return x, (mst, sk, sv)
        gblocks = jax.tree.map(lambda a: a.reshape(G, every, *a.shape[1:]), blocks)
        mamba_state = {k: state[k] for k in ("conv_x", "conv_B", "conv_C", "ssm")}
        x, (mst, sk, sv) = jax.lax.scan(
            group, x, (gblocks, mamba_state, state["shared_k"], state["shared_v"]))
        state = dict(mst)
        state["shared_k"] = sk
        state["shared_v"] = sv
    else:
        raise ValueError(cfg.family)

    logits = _lm_logits(params, cfg, x)
    return logits, state
