"""Cold-start LLM serving: express a transformer as a ColdEngine layer graph.

Each decoder block is one schedulable unit ('tblock') whose weights stream
from disk, so the paper's three knobs apply to LLM serving directly:
  K — kernel selection: `f32_direct` (read f32 master weights, cast at
      execute) vs `bf16_cast` (weights transformed to bf16 — when cached,
      HALF the disk bytes per cold read; numerically identical to the bf16
      model definition, so zero accuracy loss w.r.t. the deployed model);
  C — cache the post-transformed (bf16) weights on disk;
  P — pipeline block weight reads with execution: the first blocks compute
      while later blocks are still loading — cold first-token latency
      approaches warm prefill latency.

The graph is embed -> L× tblock -> final_norm+lm_head, all chain-shaped (the
engine's dependency model); residual adds live inside each block unit. A
sparse-expert model (``family == "moe"``) has one ``moeblock`` unit per
block instead: its attention, norms, router and the experts held on this
device (``held_experts``), whose weight names start with ``expert_``.
Its spec's config carries the layer's attention kind (``local`` or
``attn``), so blocks of different kinds fall into different shape classes
and compile apart.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.engine import LayerDef
from repro.core.registry import (
    Kernel, KERNEL_REGISTRY, LOSSY_KERNELS, LayerSpec,
)
from repro.core.staging import EXPERT_PREFIX
from repro.models import layers as L
from repro.models import moe as MOE


def _attn_residual(w, x, cfg: ArchConfig, dtype, window, yarn=None):
    """A block's first half, ``x + attn(norm(x))``, and its weights cast
    to ``dtype``."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    wd = {k: v.astype(dtype) for k, v in w.items()}
    p = {"wq": wd["wq"], "wk": wd["wk"], "wv": wd["wv"], "wo": wd["wo"]}
    if cfg.qk_norm:
        p["q_norm"], p["k_norm"] = wd["q_norm"], wd["k_norm"]
    h = L.rms_norm(x, wd["ln1"], cfg.norm_eps)
    attn, _ = L.attn_apply_seq(p, h, cfg, positions, window=window,
                               yarn=yarn)
    return x + attn, wd


def _block_forward(w: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ArchConfig,
                   dtype) -> jnp.ndarray:
    x, wd = _attn_residual(w, x, cfg, dtype, cfg.sliding_window)
    h = L.rms_norm(x, wd["ln2"], cfg.norm_eps)
    mlp = L.mlp_apply(
        {"w_gate": wd["w_gate"], "w_up": wd["w_up"], "w_down": wd["w_down"]}, h)
    return x + mlp


def _moe_block_forward(w: Dict[str, jnp.ndarray], x: jnp.ndarray,
                       spec: LayerSpec, dtype) -> jnp.ndarray:
    """A sparse-expert block of the kind its spec names: a local layer
    attends within ``sliding_window`` with plain RoPE, a full layer to
    every earlier position with ``rope_yarn``'s RoPE; then this device's
    held experts' share of the expert layer."""
    cfg = spec.config["cfg"]
    local = spec.config["kind"] == "local"
    # the residual stream in the model's dtype from the first block on
    # (the embedding's rows are bf16 values)
    x = x.astype(dtype)
    x, wd = _attn_residual(w, x, cfg, dtype,
                           cfg.sliding_window if local else None,
                           None if local else cfg.rope_yarn)
    h = L.rms_norm(x, wd["ln2"], cfg.norm_eps)
    experts = {k: wd[EXPERT_PREFIX + k] for k in ("w_gate", "w_up", "w_down")}
    experts["router"] = wd["router"]
    return x + MOE.held_experts_apply(experts, h, cfg)


class TBlockF32Direct(Kernel):
    """Read f32 master weights, cast to bf16 at execute — zero transform."""
    name = "f32_direct"
    op_type = "tblock"

    def execute(self, w, x, spec):
        return _block_forward(w, x, spec.config["cfg"], jnp.bfloat16)


class TBlockBf16(Kernel):
    """Transform = cast the block to bf16 (the deployed precision): cached
    post-transform weights are HALF the raw bytes -> ~2x faster cold reads.
    Bit-identical to f32_direct's execution (both run the block in bf16)."""
    name = "bf16_cast"
    op_type = "tblock"

    def transform(self, raw, spec):
        return {k: np.asarray(jnp.asarray(v, jnp.bfloat16))
                for k, v in raw.items()}

    def execute(self, w, x, spec):
        return _block_forward(w, x, spec.config["cfg"], jnp.bfloat16)


class MoeBlockF32Direct(Kernel):
    """A sparse-expert block from its f32 master weights, cast at execute
    to the model's dtype (bf16 as deployed)."""
    name = "f32_direct"
    op_type = "moeblock"

    def execute(self, w, x, spec):
        return _moe_block_forward(w, x, spec,
                                  jnp.dtype(spec.config["cfg"].dtype))


class MoeBlockBf16(MoeBlockF32Direct):
    """The block transformed to bf16, its deployed precision: half the
    cached bytes, the same execution as ``f32_direct``."""
    name = "bf16_cast"
    transform = TBlockBf16.transform


class EmbedDirect(Kernel):
    name = "direct"
    op_type = "embed"

    def execute(self, w, x, spec):
        return w["embed"].astype(jnp.bfloat16)[x]


class EmbedBf16(Kernel):
    name = "bf16_cast"
    op_type = "embed"

    def transform(self, raw, spec):
        return {"embed": np.asarray(jnp.asarray(raw["embed"], jnp.bfloat16))}

    def execute(self, w, x, spec):
        return w["embed"][x]


class HeadDirect(Kernel):
    name = "direct"
    op_type = "lmhead"

    def execute(self, w, x, spec):
        cfg = spec.config["cfg"]
        h = L.rms_norm(x, w["final_norm"].astype(jnp.bfloat16), cfg.norm_eps)
        return (h @ w["w"].astype(jnp.bfloat16)).astype(jnp.float32)


class HeadBf16(Kernel):
    name = "bf16_cast"
    op_type = "lmhead"

    def transform(self, raw, spec):
        return {k: np.asarray(jnp.asarray(v, jnp.bfloat16))
                for k, v in raw.items()}

    def execute(self, w, x, spec):
        cfg = spec.config["cfg"]
        h = L.rms_norm(x, w["final_norm"], cfg.norm_eps)
        return (h @ w["w"]).astype(jnp.float32)


def _dequant(w: Dict[str, jnp.ndarray], spec: LayerSpec
             ) -> Dict[str, jnp.ndarray]:
    """Expand a companion-key weight dict (``repro.quant`` convention) to a
    plain dict: int8/int4 tensors dequantized to f32 in-graph, everything
    else passed through. Logical K of a packed int4 tensor comes from the
    layer spec (static under jit)."""
    out: Dict[str, jnp.ndarray] = {}
    for k, v in w.items():
        if k.endswith(":qscale") or k.endswith(":qzero"):
            continue
        if k.endswith(":q8"):
            base = k[: -len(":q8")]
            out[base] = v.astype(jnp.float32) * w[base + ":qscale"]
        elif k.endswith(":q4"):
            base = k[: -len(":q4")]
            K = spec.weight_shapes[base][0]
            p = v.astype(jnp.int32)
            lo = p & 0x0F
            hi = (p >> 4) & 0x0F
            lo = jnp.where(lo >= 8, lo - 16, lo)
            hi = jnp.where(hi >= 8, hi - 16, hi)
            q = jnp.stack([lo, hi], axis=1).reshape(
                2 * p.shape[0], p.shape[1])[:K]
            out[base] = q.astype(jnp.float32) * w[base + ":qscale"]
        else:
            out[k] = v
    return out


class TBlockInt8(Kernel):
    """Quantized transform cache for a decoder block: every 2-D matmul
    operand stored as per-channel int8 (+f32 scales in the extent header),
    1-D norm gains as bf16 — ~4x fewer cold cache bytes than f32, ~2x
    fewer than bf16_cast. Execution dequantizes in-graph and runs the same
    bf16 block forward. Lossy (bounded per-weight error), so gated behind
    the engine's ``allow_lossy``."""
    name = "int8"
    op_type = "tblock"
    bits = 8

    def transform(self, raw, spec):
        from repro import quant

        out = quant.quantize_weights(raw, bits=self.bits)
        return {k: (np.asarray(jnp.asarray(v, jnp.bfloat16))
                    if getattr(v, "ndim", 0) == 1 else v)
                for k, v in out.items()}

    def execute(self, w, x, spec):
        return _block_forward(_dequant(w, spec), x, spec.config["cfg"],
                              jnp.bfloat16)


class TBlockInt4(TBlockInt8):
    """Nibble-packed int4 block cache: ~8x fewer cold cache bytes than f32
    — the last rung of the read-bytes ladder; coarser than int8."""
    name = "int4"
    bits = 4


class HeadInt8(Kernel):
    """lm_head with the vocab-projection matrix as per-channel int8."""
    name = "int8"
    op_type = "lmhead"
    bits = 8

    def transform(self, raw, spec):
        from repro import quant

        out = quant.quantize_weights(raw, bits=self.bits)
        return {k: (np.asarray(jnp.asarray(v, jnp.bfloat16))
                    if getattr(v, "ndim", 0) == 1 else v)
                for k, v in out.items()}

    def execute(self, w, x, spec):
        cfg = spec.config["cfg"]
        wd = _dequant(w, spec)
        h = L.rms_norm(x, wd["final_norm"].astype(jnp.bfloat16), cfg.norm_eps)
        return (h @ wd["w"].astype(jnp.bfloat16)).astype(jnp.float32)


class HeadInt4(HeadInt8):
    name = "int4"
    bits = 4


KERNEL_REGISTRY.setdefault("tblock", [TBlockF32Direct(), TBlockBf16()])
# no lossy variant: a sparse-expert block stages in its deployed precision
KERNEL_REGISTRY.setdefault("moeblock", [MoeBlockF32Direct(), MoeBlockBf16()])
KERNEL_REGISTRY.setdefault("embed", [EmbedDirect(), EmbedBf16()])
KERNEL_REGISTRY.setdefault("lmhead", [HeadDirect(), HeadBf16()])
# quantized variants are lossy: eligible only under the engine's allow_lossy
# (embed stays unquantized — it's a gather, not a matmul, and its rows feed
# the residual stream directly)
LOSSY_KERNELS.setdefault("tblock", [TBlockInt8(), TBlockInt4()])
LOSSY_KERNELS.setdefault("lmhead", [HeadInt8(), HeadInt4()])


_OP_TYPES = {"embed": "embed", "lm_head": "lmhead"}


def _layer_names(cfg: ArchConfig) -> List[str]:
    return (["embed"] + [f"block{i:03d}" for i in range(cfg.num_layers)]
            + ["lm_head"])


def _specs(cfg: ArchConfig, shapes: Dict[str, Dict[str, tuple]]
           ) -> List[LayerSpec]:
    """The cold graph's layer specs from each layer's weight shapes."""
    kinds = cfg.layer_kinds()
    out = []
    for name in _layer_names(cfg):
        if name in _OP_TYPES:
            out.append(LayerSpec(name, _OP_TYPES[name], {"cfg": cfg},
                                 shapes[name]))
        elif cfg.family == "moe":
            out.append(LayerSpec(name, "moeblock",
                                 {"cfg": cfg, "kind": kinds[int(name[5:])]},
                                 shapes[name]))
        else:
            out.append(LayerSpec(name, "tblock", {"cfg": cfg}, shapes[name]))
    return out


def _block_keys(cfg: ArchConfig) -> List[Tuple[Tuple[str, ...], str]]:
    """(path in the T-format block params, unit weight name) of a block."""
    keys = ([(("ln1",), "ln1"), (("ln2",), "ln2")]
            + [(("attn", k), k) for k in ("wq", "wk", "wv", "wo")])
    if cfg.family == "moe":
        keys += [(("moe", "router"), "router")]
        keys += [(("moe", k), EXPERT_PREFIX + k)
                 for k in ("w_gate", "w_up", "w_down")]
    else:
        keys += [(("mlp", k), k) for k in ("w_gate", "w_up", "w_down")]
    if cfg.qk_norm:
        keys += [(("attn", "q_norm"), "q_norm"), (("attn", "k_norm"), "k_norm")]
    return keys


def _layer_weights(cfg: ArchConfig, params) -> Dict[str, Dict[str, jax.Array]]:
    """{layer: {weight: array}} of the cold graph, cut from T-format params
    (traceable, so ``jax.eval_shape`` gives the shapes at no cost)."""
    blocks = params["blocks"]
    out = {"embed": {"embed": params["embed"]}}
    keys = _block_keys(cfg)
    for i in range(cfg.num_layers):
        bw = {}
        for path, name in keys:
            leaf = blocks
            for p in path:
                leaf = leaf[p]
            bw[name] = leaf[i]
        out[f"block{i:03d}"] = bw
    head_w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out["lm_head"] = {"w": head_w, "final_norm": params["final_norm"]}
    return out


def build_llm_graph_specs(cfg: ArchConfig) -> List[LayerSpec]:
    """The engine layer specs of ``cfg``'s cold graph, from parameter
    shapes alone — nothing is allocated, so this serves at any width."""
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda: _layer_weights(
        cfg, T.init_params(jax.random.PRNGKey(0), cfg)))
    return _specs(cfg, {n: {k: tuple(v.shape) for k, v in ws.items()}
                        for n, ws in shapes.items()})


def build_llm_graph(cfg: ArchConfig, params) -> Tuple[List[LayerDef], np.ndarray]:
    """Convert dense- or moe-family transformer params (from
    T.init_params) into an engine graph + an example token batch. Raw
    storage is f32 (the master checkpoint); execution is bf16 (the
    deployed precision)."""
    assert cfg.family in ("dense", "moe"), \
        "the cold-LLM graph takes dense and sparse-expert decoders"
    layers = _layer_weights(cfg, params)
    weights = {name: {k: np.asarray(jnp.asarray(v, jnp.float32))
                      for k, v in layers[name].items()}
               for name in _layer_names(cfg)}
    specs = _specs(cfg, {n: {k: tuple(v.shape) for k, v in w.items()}
                         for n, w in weights.items()})
    return ([LayerDef(spec=s, weights=weights[s.name]) for s in specs],
            example_tokens(cfg.vocab_size))


def example_tokens(vocab_size: int, length: int = 64) -> np.ndarray:
    """The graph's example token batch: (1, length) int32, fixed seed."""
    rng = np.random.default_rng(0)
    return rng.integers(0, vocab_size, size=(1, length)).astype(np.int32)


def named_llm_graph(arch: str, *, seed: int = 0, num_layers: int = 0
                    ) -> Tuple[List[LayerDef], np.ndarray]:
    """A registered config's cold graph at its published widths, weights
    from ``T.init_params`` with ``seed`` (``num_layers`` > 0 cuts depth).
    A ``FrontDoor.add_model`` builder: deterministic for a given seed."""
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config(arch)
    if num_layers:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return build_llm_graph(cfg, params)


def tiny_llm_graph(num_layers: int = 8, *, seed: int = 0
                   ) -> Tuple[List[LayerDef], np.ndarray]:
    """A small dense graph with ``num_layers`` byte-identical decoder blocks
    — the canonical shape-class workload for tests and the
    ``plan_generation`` benchmark: all tblocks fall into ONE shape class, so
    ``decide()`` should profile/compile each kernel once, not L times."""
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("smollm-360m").reduced(
        num_layers=num_layers, d_model=128, d_ff=256, num_heads=2,
        num_kv_heads=1, head_dim=64, vocab_size=512)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return build_llm_graph(cfg, params)
