"""Mixture-of-Experts layers: a top-k router and the experts' SwiGLU.

Two layers:

* ``held_experts_apply``, for a config that names the experts held here
  (expert parallelism, one device's share): every held expert runs over
  every token, weighted by its routing weight, so it is dropless at any
  token count. The cold path's ``moeblock`` units and decode take it.
* the capacity layer (``_local_moe``): tokens sorted by assigned expert,
  each expert a block of ``C`` rows (``cfg.moe_capacity_factor`` sizes
  ``C``); tokens beyond an expert's capacity are dropped. Compute is
  proportional to the *active* expert parameters.

Baseline sharding (see DESIGN.md §5): expert weights are sharded over the
``model`` mesh axis along the per-expert ffn dimension (expert tensor
parallelism) which lowers for any expert count; expert-parallel all_to_all is
explored as a hillclimb variant in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init


# ---------------------------------------------------------------------------
# grouped matmul with a memory-sane VJP
# ---------------------------------------------------------------------------
# The default ragged_dot transpose rule materializes a dense (groups, m, k)
# tensor for the weight gradient (7.5 GiB/device for granite train_4k). Both
# cotangents are themselves grouped matmuls, so express them that way:
#   dx[i]  = dy[i] @ w[g(i)]^T          -> ragged_dot with transposed rhs
#   dw[g]  = x_g^T @ dy_g               -> ragged_dot_general, ragged dim
#                                          contracting (MegaBlocks dsd/sdd).
@jax.custom_vjp
def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array):
    return jax.lax.ragged_dot(x, w, group_sizes)


def _gm_fwd(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes), (x, w, group_sizes)


def _gm_bwd(res, dy):
    x, w, gs = res
    dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), gs)
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[],
    )
    dw = jax.lax.ragged_dot_general(
        x, dy.astype(x.dtype), gs, dn
    ).astype(w.dtype)
    return dx.astype(x.dtype), dw, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def moe_init(key, cfg) -> dict:
    """The router over all ``num_experts`` and the weights of the experts
    held here (``cfg.experts_here``), stacked in ``held_experts`` order."""
    d, ff, E, H = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.experts_here
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    return {
        "router": _dense_init(ks[0], (d, E), jnp.float32, scale),
        "w_gate": (jax.random.normal(ks[1], (H, d, ff), jnp.float32) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (H, d, ff), jnp.float32) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (H, ff, d), jnp.float32) / math.sqrt(ff)).astype(dt),
    }


def held_experts_apply(p: dict, x: jax.Array, cfg) -> jax.Array:
    """The share of an expert layer that the experts held here give:
    x (..., d) -> (..., d).

    The router spans all ``cfg.num_experts``: softmax, the top ``top_k``,
    renormalised to sum to one. Each held expert
    (``cfg.held_experts``; every expert where empty) adds
    ``p_e * SwiGLU_e(x)`` for the tokens that picked it and nothing for
    the others; what the experts held elsewhere give is left out (expert
    parallelism without its exchange). Every held expert runs over every
    token, weighted by its routing weight (zero where not picked), so no
    token is ever dropped, whatever the routing. ``p`` holds ``router``
    (d, E) and ``w_gate``/``w_up`` (H, d, ff), ``w_down`` (H, ff, d)."""
    lead, d = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, d)
    logits = jnp.dot(xf, p["router"].astype(xf.dtype),
                     preferred_element_type=jnp.float32)      # (T, E)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    held = jnp.asarray(cfg.held_experts or range(cfg.num_experts), jnp.int32)
    gate = jnp.sum(jnp.where(top_e[:, :, None] == held, top_p[:, :, None],
                             0.0), axis=1)                     # (T, H)
    h = (jax.nn.silu(jnp.einsum("td,hdf->thf", xf, p["w_gate"]))
         * jnp.einsum("td,hdf->thf", xf, p["w_up"]))
    h = h * gate[:, :, None].astype(h.dtype)
    y = jnp.einsum("thf,hfd->td", h, p["w_down"])
    return y.reshape(*lead, d)


def _local_moe(xf: jax.Array, router, w_gate, w_up, w_down, cfg):
    """Token-local MoE over a flat token block (T, d). Used directly on CPU
    and as the shard_map body on a mesh — the sort over tokens then stays
    *per data shard* (a global argsort over a sharded dim would force SPMD
    to all-gather every token)."""
    T, d = xf.shape
    E, k = cfg.num_experts, cfg.top_k

    logits = (xf.astype(jnp.float32) @ router)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balance aux loss
    frac = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0
    )
    aux = jnp.sum(frac * jnp.mean(probs, axis=0)) * E

    # ---- sort + capacity-sliced grouped GEMM ------------------------------
    # (jax.lax.ragged_dot lowers to a dense masked einsum on both CPU and
    # TPU-XLA — an (E, T·k, d) monster. The sorted/sliced scan below lowers
    # to E blockwise (C,d)x(d,ff) matmuls, which is what the Pallas gmm
    # kernel implements natively on TPU.)
    flat_e = top_e.reshape(T * k)
    perm = jnp.argsort(flat_e)                      # stable sort by expert id
    token_of = perm // k                            # original token index
    xs = xf[token_of]                               # (T*k, d), expert-sorted
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    cap = getattr(cfg, "moe_capacity_factor", 2.0)
    C = int(math.ceil(T * k / E * cap / 8.0)) * 8
    C = max(8, min(C, T * k))
    ys = _grouped_ffn(xs, group_sizes, w_gate, w_up, w_down, C)

    inv = jnp.argsort(perm)
    y = ys[inv].reshape(T, k, d)
    y = jnp.sum(y * top_p[..., None].astype(y.dtype), axis=1)
    return y, aux


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_ffn(xs, group_sizes, w_gate, w_up, w_down, C: int):
    """Expert-blocked SwiGLU over expert-sorted tokens.

    xs (M, d) sorted by expert; each expert e owns rows
    [offset_e, offset_e + size_e). A scan over experts dynamic-slices a
    static-capacity block of C rows, runs the expert FFN, masks rows beyond
    size_e, and accumulates back. Tokens beyond capacity are dropped
    (standard capacity-factor semantics; cfg.moe_capacity_factor sizes C).

    Custom VJP: the autodiff transpose of the block dynamic-slice would add a
    full (M,d) cotangent buffer per expert iteration (O(E·M·d) traffic); the
    hand-written backward recomputes each block (flash-style) and accumulates
    the cotangent through the same C-row window.
    """
    y, _ = _grouped_ffn_fwd(xs, group_sizes, w_gate, w_up, w_down, C)
    return y


def _gffn_blocks(xs_pad, offsets, group_sizes, w_gate, w_up, w_down, C):
    M_pad, d = xs_pad.shape
    d_out = w_down.shape[-1]
    E = group_sizes.shape[0]

    def body(_, inp):
        off, size, wg, wu, wd = inp
        blk = jax.lax.dynamic_slice(xs_pad, (off, 0), (C, d))
        h = jax.nn.silu(blk @ wg) * (blk @ wu)
        yb = h @ wd
        mask = (jnp.arange(C) < size)[:, None]
        return None, jnp.where(mask, yb, 0)

    _, ys = jax.lax.scan(body, None, (offsets, group_sizes, w_gate, w_up, w_down))
    rows = (offsets[:, None] + jnp.arange(C)[None, :]).reshape(-1)
    y = jnp.zeros((M_pad, d_out), xs_pad.dtype).at[rows].add(
        ys.reshape(E * C, d_out))
    return y


def _grouped_ffn_fwd(xs, group_sizes, w_gate, w_up, w_down, C):
    M, d = xs.shape
    offsets = jnp.cumsum(group_sizes) - group_sizes
    xs_pad = jnp.pad(xs, ((0, C), (0, 0)))
    y = _gffn_blocks(xs_pad, offsets, group_sizes, w_gate, w_up, w_down, C)[:M]
    return y, (xs, group_sizes, w_gate, w_up, w_down)


def _grouped_ffn_bwd(C, res, dy):
    xs, group_sizes, w_gate, w_up, w_down = res
    M, d = xs.shape
    offsets = jnp.cumsum(group_sizes) - group_sizes
    xs_pad = jnp.pad(xs, ((0, C), (0, 0)))
    dy_pad = jnp.pad(dy, ((0, C), (0, 0)))

    def body(dxs, inp):
        off, size, wg, wu, wd = inp
        mask = (jnp.arange(C) < size)[:, None]
        blk = jax.lax.dynamic_slice(xs_pad, (off, 0), (C, d))
        dyb = jax.lax.dynamic_slice(dy_pad, (off, 0), (C, dy.shape[1]))
        dyb = jnp.where(mask, dyb, 0)
        g = blk @ wg
        u = blk @ wu
        sg = jax.nn.sigmoid(g.astype(jnp.float32))
        silu_g = (g.astype(jnp.float32) * sg).astype(g.dtype)
        h = silu_g * u
        dh = dyb @ wd.T
        dwd = h.T @ dyb
        du = dh * silu_g
        dsilu = (sg * (1 + g.astype(jnp.float32) * (1 - sg))).astype(g.dtype)
        dg = dh * u * dsilu
        dwg = blk.T @ dg
        dwu = blk.T @ du
        dblk = dg @ wg.T + du @ wu.T
        cur = jax.lax.dynamic_slice(dxs, (off, 0), (C, d))
        dxs = jax.lax.dynamic_update_slice(dxs, cur + dblk, (off, 0))
        return dxs, (dwg, dwu, dwd)

    dxs0 = jnp.zeros_like(xs_pad)
    dxs, (dwg, dwu, dwd) = jax.lax.scan(
        body, dxs0, (offsets, group_sizes, w_gate, w_up, w_down))
    return dxs[:M], None, dwg, dwu, dwd


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def _mesh_ctx():
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


# per-shard token block size: longer streams are processed in sequential
# blocks so the sorted/sliced buffers stay bounded (32k-prefill MoE would
# otherwise hold (T·k, d) + (E, C, d) live at once)
MOE_TOKEN_BLOCK = 16_384


def _blocked_local_moe(xf, router, wg, wu, wd, cfg):
    T = xf.shape[0]
    if T <= MOE_TOKEN_BLOCK:
        return _local_moe(xf, router, wg, wu, wd, cfg)
    nb = (T + MOE_TOKEN_BLOCK - 1) // MOE_TOKEN_BLOCK
    while T % nb != 0:
        nb += 1
    blk = T // nb
    xb = xf.reshape(nb, blk, xf.shape[1])

    def body(_, xs):
        y, aux = _local_moe(xs, router, wg, wu, wd, cfg)
        return None, (y, aux)

    _, (ys, auxs) = jax.lax.scan(body, None, xb)
    return ys.reshape(T, -1), jnp.mean(auxs)


def moe_apply(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).

    aux_loss is the standard switch-transformer load-balance loss
    (mean_e frac_tokens_e * mean_router_prob_e * E). A config that names
    the experts held here (``held_experts``) takes the dropless
    ``held_experts_apply`` instead, with no aux loss.

    On a mesh, tokens are routed *per data shard* under shard_map (expert
    weights ff-sharded over `model` — expert tensor parallelism) with a psum
    over `model` for the down-projection partial sums.
    """
    if cfg.held_experts:
        return held_experts_apply(p, x, cfg), jnp.zeros((), jnp.float32)
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    mesh = _mesh_ctx()
    if mesh is None:
        y, aux = _blocked_local_moe(xf, p["router"], p["w_gate"], p["w_up"],
                                    p["w_down"], cfg)
        return y.reshape(B, S, d), aux

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    names = dict(mesh.shape)
    db = tuple(a for a in ("pod", "data") if a in names)
    ff_ok = cfg.d_ff % names.get("model", 1) == 0
    mdl = "model" if ff_ok and "model" in names else None
    dsize = math.prod(names[a] for a in db) if db else 1
    tok_axes = db if db and (B * S) % dsize == 0 and dsize > 1 else None

    def body(xl, router, wg, wu, wd):
        y, aux = _blocked_local_moe(xl, router, wg, wu, wd, cfg)
        if mdl is not None:
            y = jax.lax.psum(y, mdl)
        if tok_axes:
            aux = jax.lax.pmean(aux, tok_axes)
        return y, aux

    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(
            P(tok_axes, None),
            P(None, None),
            P(None, None, mdl), P(None, None, mdl), P(None, mdl, None),
        ),
        out_specs=(P(tok_axes, None), P()),
        check_vma=False,
    )(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(B, S, d), aux
