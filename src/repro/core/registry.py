"""Operator/kernel registry — §3.1.1 "one operator, many kernels".

A *kernel* is one concrete implementation of an operator, with its own
weights-transformation stage. Mirroring ncnn's 28 conv kernels, each operator
type registers several kernels with different (transform cost, execution
cost, transformed size) trade-offs; the scheduler picks per layer.

Kernels expose:
  transform(raw)        raw weight dict -> execution-format weight dict
  execute(w, x)         jnp forward (jitted once per shape by the engine)
  supports(spec)        static applicability predicate
"""
from __future__ import annotations

import dataclasses
import enum
import hashlib
import json
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class OpKind(enum.Enum):
    READ = "read"
    TRANSFORM = "transform"
    STAGE = "stage"      # host -> device weight transfer (device_put)
    EXECUTE = "execute"
    COMPILE = "compile"  # GPU-analogue stage: jit/"shader" compilation


@dataclass(frozen=True)
class LayerSpec:
    """One schedulable unit of the model (a layer, in the paper's terms)."""
    name: str
    op_type: str                  # 'conv2d' | 'linear' | 'stateless' | ...
    config: Dict[str, Any] = field(default_factory=dict)
    # weight name -> shape; empty for stateless units (e.g. attention core)
    weight_shapes: Dict[str, Tuple[int, ...]] = field(default_factory=dict)

    @property
    def weight_bytes(self) -> int:
        return sum(4 * math.prod(s) for s in self.weight_shapes.values())


@dataclass(frozen=True)
class Operation:
    """One stage of one layer's kernel — the scheduler's unit of work."""
    layer: str
    kind: OpKind
    index: int  # layer index in the chain


# ---------------------------------------------------------------------------
# shape classes — profile/compile equivalence between layers
# ---------------------------------------------------------------------------
def _canon(v: Any) -> Any:
    """Deterministic, JSON-stable canonicalization of config values."""
    if isinstance(v, bool) or v is None or isinstance(v, (int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_canon(x) for x in v]
    if isinstance(v, dict):
        return [[str(k), _canon(v[k])] for k in sorted(v, key=str)]
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return [type(v).__name__, _canon(dataclasses.asdict(v))]
    if isinstance(v, np.dtype):
        return str(v)
    return repr(v)


def shape_class_key(
    spec: LayerSpec,
    *,
    input_shape: Optional[Tuple[int, ...]] = None,
    input_dtype: Optional[str] = None,
    weight_dtypes: Optional[Dict[str, str]] = None,
) -> str:
    """Canonical shape-class identity of a layer: two layers with the same
    key are interchangeable for profiling and compilation — same op_type,
    same weight shapes/dtypes, same kernel-relevant config, and (when
    given) same input avatar. Byte-identical decoder blocks of an LLM graph
    all land in one class, so ``decide()`` profiles/compiles ONE
    representative and fans the result out.

    Stateless units wrap arbitrary Python callables whose identity the spec
    cannot see, so they never share: their key includes the layer name.
    """
    if spec.op_type == "stateless":
        payload: List[Any] = ["stateless", spec.name]
    else:
        payload = [
            spec.op_type,
            [[k, list(spec.weight_shapes[k])] for k in sorted(spec.weight_shapes)],
            _canon(spec.config),
        ]
    payload.append([
        list(input_shape) if input_shape is not None else None,
        input_dtype,
        _canon(weight_dtypes) if weight_dtypes else None,
    ])
    blob = json.dumps(payload, sort_keys=False, separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()[:20]


def shape_class_sibling_key(
    spec: LayerSpec,
    *,
    input_shape: Optional[Tuple[int, ...]] = None,
    input_dtype: Optional[str] = None,
    weight_dtypes: Optional[Dict[str, str]] = None,
) -> Optional[str]:
    """Batch-agnostic relative of :func:`shape_class_key`: the leading
    (batch) dim of the input avatar is replaced by a sentinel, so classes
    identical up to batch size share one sibling key. The ProfileDB uses it
    for *approximate* profile fan-out (``approx=True``): a layer profiled
    at batch 1 seeds the candidate costs for the same layer at batch 4 —
    per-element op costs barely shift with batch on these graphs, and a
    stale estimate only mis-ranks candidates, never breaks correctness.

    ``None`` when there is no input avatar to widen (nothing to
    approximate over) or for stateless units (never shared)."""
    if spec.op_type == "stateless" or input_shape is None or not input_shape:
        return None
    payload: List[Any] = [
        spec.op_type,
        [[k, list(spec.weight_shapes[k])] for k in sorted(spec.weight_shapes)],
        _canon(spec.config),
    ]
    payload.append([
        ["B"] + list(input_shape[1:]),
        input_dtype,
        _canon(weight_dtypes) if weight_dtypes else None,
    ])
    blob = json.dumps(payload, sort_keys=False, separators=(",", ":"))
    return "~" + hashlib.sha1(blob.encode()).hexdigest()[:20]


class Kernel:
    name: str = "base"
    op_type: str = "generic"

    def supports(self, spec: LayerSpec) -> bool:
        return True

    def transform(self, raw: Dict[str, np.ndarray], spec: LayerSpec) -> Dict[str, np.ndarray]:
        """Raw -> execution-ready weights. Runs on host (little cores)."""
        return raw

    def execute(self, w: Dict[str, jnp.ndarray], x: jnp.ndarray, spec: LayerSpec) -> jnp.ndarray:
        raise NotImplementedError

    def program(self, spec: LayerSpec) -> Callable:
        """``fn(w, x)`` running this kernel for ``spec``, named
        ``<layer kind>_<kernel>``: jitted, it is the XLA module
        ``jit_<layer kind>_<kernel>`` in a profiler trace."""
        def fn(w, x):
            return self.execute(w, x, spec)

        fn.__name__ = fn.__qualname__ = f"{spec.op_type}_{self.name}"
        return fn

    def __repr__(self):
        return f"<Kernel {self.op_type}/{self.name}>"


# ---------------------------------------------------------------------------
# linear kernels
# ---------------------------------------------------------------------------
class LinearDirect(Kernel):
    """Plain x @ W — zero transform (the paper's '3x3s1'/'general' analogue)."""
    name = "direct"
    op_type = "linear"

    def execute(self, w, x, spec):
        y = x @ w["w"]
        if "b" in w:
            y = y + w["b"]
        return y


class LinearPacked(Kernel):
    """MXU block-tiled layout: W (K,N) -> (N/bn, K/bk, bk, bn), padded to
    multiples of 128. Fast execution on the Pallas blocked-matmul kernel
    (repro.kernels.matmul) but the packing pass is a real transformation cost
    — the sgemm_pack4 analogue."""
    name = "packed"
    op_type = "linear"
    bk = 128
    bn = 128

    def transform(self, raw, spec):
        w = raw["w"]
        K, N = w.shape
        bk, bn = self.bk, self.bn
        Kp = (K + bk - 1) // bk * bk
        Np = (N + bn - 1) // bn * bn
        wp = np.zeros((Kp, Np), w.dtype)
        wp[:K, :N] = w
        packed = np.ascontiguousarray(
            wp.reshape(Kp // bk, bk, Np // bn, bn).transpose(2, 0, 1, 3)
        )
        out = {"w_packed": packed, "orig_kn": np.array([K, N], np.int64)}
        if "b" in raw:
            out["b"] = raw["b"]
        return out

    def execute(self, w, x, spec):
        packed = w["w_packed"]  # (nN, nK, bk, bn)
        K, N = spec.config["in_features"], spec.config["out_features"]
        lead = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1])
        M = xf.shape[0]
        Kp = packed.shape[1] * packed.shape[2]
        if Kp != K:
            xf = jnp.pad(xf, ((0, 0), (0, Kp - K)))
        xb = xf.reshape(M, packed.shape[1], packed.shape[2])
        # blocked contraction consuming the packed layout directly
        y = jnp.einsum("mkc,nkcd->mnd", xb, packed)
        y = y.reshape(M, packed.shape[0] * packed.shape[3])[:, :N]
        if "b" in w:
            y = y + w["b"]
        return y.reshape(*lead, N)


class LinearLowPrecision(Kernel):
    """bf16-converted weights: halves the bytes read back from the
    transformed-weights cache (a disk-I/O/exec trade, like the paper's pack4
    variants). Matmul runs in bf16 with f32 accumulation — bitwise-identical
    outputs are NOT guaranteed, so this kernel is only eligible when the
    engine is configured with ``allow_lossy`` (off by default: the paper's
    zero-accuracy-loss principle)."""
    name = "bf16"
    op_type = "linear"

    def transform(self, raw, spec):
        out = {"w": np.asarray(jnp.asarray(raw["w"], jnp.bfloat16))}
        if "b" in raw:
            out["b"] = raw["b"]
        return out

    def execute(self, w, x, spec):
        y = jnp.dot(x.astype(jnp.bfloat16), w["w"],
                    preferred_element_type=jnp.float32)
        if "b" in w:
            y = y + w["b"]
        return y


class LinearInt8(Kernel):
    """Per-channel symmetric int8 cache entry (``repro.quant`` companion
    keys): ~4x fewer cold cache bytes than f32, ~2x fewer than bf16. The
    matmul consumes the int8 tensor directly and the per-output-channel
    scale is factored out of the K loop (``(x @ q) * scale``) — the jnp
    twin of the fused Pallas kernel ``repro.kernels.quant
    .matmul_dequant_int8``. Lossy (bounded by scale/2 per weight), so
    gated behind ``allow_lossy`` like the bf16 kernel."""
    name = "int8"
    op_type = "linear"
    bits = 8

    def transform(self, raw, spec):
        from repro import quant

        out = quant.quantize_weight("w", np.asarray(raw["w"], np.float32),
                                    bits=self.bits)
        if "b" in raw:
            out["b"] = raw["b"]
        return out

    def execute(self, w, x, spec):
        y = jnp.dot(x, w["w:q8"].astype(jnp.float32),
                    preferred_element_type=jnp.float32) * w["w:qscale"]
        if "b" in w:
            y = y + w["b"]
        return y


class LinearInt4(LinearInt8):
    """Nibble-packed int4 cache entry: ~8x fewer cold cache bytes than f32.
    Unpacks in-graph (the jnp twin of ``matmul_dequant_int4``) then runs
    the same scale-factored matmul. Coarser than int8 — last rung of the
    read-bytes ladder."""
    name = "int4"
    bits = 4

    def execute(self, w, x, spec):
        p = w["w:q4"].astype(jnp.int32)
        lo = p & 0x0F
        hi = (p >> 4) & 0x0F
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        K = spec.weight_shapes["w"][0]
        q = jnp.stack([lo, hi], axis=1).reshape(
            2 * p.shape[0], p.shape[1])[:K].astype(jnp.float32)
        y = jnp.dot(x, q, preferred_element_type=jnp.float32) * w["w:qscale"]
        if "b" in w:
            y = y + w["b"]
        return y


# ---------------------------------------------------------------------------
# conv2d kernels (NHWC, filters OIHW in raw checkpoints — ncnn-style)
# ---------------------------------------------------------------------------
def _conv_dims(spec):
    c = spec.config
    return c["kernel"], c.get("stride", 1), c.get("padding", "SAME")


class ConvDirect(Kernel):
    """lax.conv_general_dilated on raw OIHW filters — zero transform."""
    name = "direct"
    op_type = "conv2d"

    def execute(self, w, x, spec):
        k, s, p = _conv_dims(spec)
        y = jax.lax.conv_general_dilated(
            x, w["w"], window_strides=(s, s), padding=p,
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
        )
        if "b" in w:
            y = y + w["b"]
        return y


class ConvIm2col(Kernel):
    """im2col + sgemm: filters reshaped (O,I,kh,kw) -> (I*kh*kw, O). Cheap
    transform, fast-ish exec (the paper's sgemm kernels)."""
    name = "im2col_sgemm"
    op_type = "conv2d"

    def transform(self, raw, spec):
        w = raw["w"]  # (O, I, kh, kw)
        O, I, kh, kw = w.shape
        wt = np.ascontiguousarray(w.transpose(2, 3, 1, 0).reshape(kh * kw * I, O))
        out = {"w_mat": wt}
        if "b" in raw:
            out["b"] = raw["b"]
        return out

    def execute(self, w, x, spec):
        k, s, p = _conv_dims(spec)
        N, C = x.shape[0], x.shape[-1]
        patches = jax.lax.conv_general_dilated_patches(
            x, (k, k), (s, s), p, dimension_numbers=("NHWC", "OIHW", "NHWC"))
        Ho, Wo = patches.shape[1], patches.shape[2]
        # conv_general_dilated_patches returns features ordered (C, kh, kw)
        pm = patches.reshape(N * Ho * Wo, C, k, k).transpose(0, 2, 3, 1)
        pm = pm.reshape(N * Ho * Wo, k * k * C)
        y = pm @ w["w_mat"]
        y = y.reshape(N, Ho, Wo, -1)
        if "b" in w:
            y = y + w["b"]
        return y


class ConvWinograd(Kernel):
    """Winograd F(2x2, 3x3): filter transform (O,I,3,3) -> (16, I, O) done
    offline/on little cores (the paper's flagship heavy transform, Fig. 3);
    execution is 16 batched (I,O) matmuls over 4x4 input tiles — maps onto
    the MXU (Pallas kernel: repro.kernels.conv_winograd)."""
    name = "winograd_f2x3"
    op_type = "conv2d"

    G = np.array(
        [[1.0, 0.0, 0.0],
         [0.5, 0.5, 0.5],
         [0.5, -0.5, 0.5],
         [0.0, 0.0, 1.0]], np.float32)
    Bt = np.array(
        [[1, 0, -1, 0],
         [0, 1, 1, 0],
         [0, -1, 1, 0],
         [0, 1, 0, -1]], np.float32)
    At = np.array(
        [[1, 1, 1, 0],
         [0, 1, -1, -1]], np.float32)

    def supports(self, spec):
        k, s, _ = _conv_dims(spec)
        return k == 3 and s == 1

    def transform(self, raw, spec):
        w = raw["w"]  # (O, I, 3, 3)
        O, I, _, _ = w.shape
        # U = G g G^T per (O, I): g (O,I,3,3) -> (O,I,4,4)
        U = np.einsum("ab,oibc,dc->oiad", self.G, w, self.G, optimize=True)
        Ut = np.ascontiguousarray(U.transpose(2, 3, 1, 0).reshape(16, I, O))
        out = {"w_wino": Ut}
        if "b" in raw:
            out["b"] = raw["b"]
        return out

    def execute(self, w, x, spec):
        U = w["w_wino"]  # (16, I, O)
        N, H, W_, C = x.shape
        pad_h = (-H) % 2 + 1
        pad_w = (-W_) % 2 + 1
        xp = jnp.pad(x, ((0, 0), (1, pad_h), (1, pad_w), (0, 0)))
        Hp, Wp = xp.shape[1], xp.shape[2]
        nth, ntw = (Hp - 2) // 2, (Wp - 2) // 2
        # extract overlapping 4x4 tiles with stride 2
        idx_h = (jnp.arange(nth) * 2)[:, None] + jnp.arange(4)[None, :]
        idx_w = (jnp.arange(ntw) * 2)[:, None] + jnp.arange(4)[None, :]
        tiles = xp[:, idx_h][:, :, :, idx_w]        # (N, nth, 4, ntw, 4, C)
        tiles = tiles.transpose(0, 1, 3, 2, 4, 5)   # (N, nth, ntw, 4, 4, C)
        Bt = jnp.asarray(self.Bt)
        At = jnp.asarray(self.At)
        V = jnp.einsum("ab,nhwbcq,dc->nhwadq", Bt, tiles, Bt)  # (N,h,w,4,4,C)
        V = V.reshape(N * nth * ntw, 16, C).transpose(1, 0, 2)  # (16, T, C)
        M = jnp.einsum("ktc,kco->kto", V, U)                    # (16, T, O)
        O_ = M.shape[-1]
        M = M.transpose(1, 0, 2).reshape(N, nth, ntw, 4, 4, O_)
        Y = jnp.einsum("ab,nhwbcq,dc->nhwadq", At, M, At)       # (N,h,w,2,2,O)
        Y = Y.transpose(0, 1, 3, 2, 4, 5).reshape(N, nth * 2, ntw * 2, O_)
        Y = Y[:, :H, :W_, :]
        if "b" in w:
            Y = Y + w["b"]
        return Y


# ---------------------------------------------------------------------------
# stateless units (attention core, pooling, activations…): execute only
# ---------------------------------------------------------------------------
class StatelessKernel(Kernel):
    name = "fn"
    op_type = "stateless"

    def __init__(self, fn: Callable, name: str = "fn"):
        self.fn = fn
        self.name = name

    def execute(self, w, x, spec):
        return self.fn(x)


KERNEL_REGISTRY: Dict[str, List[Kernel]] = {
    "linear": [LinearDirect(), LinearPacked()],
    "conv2d": [ConvDirect(), ConvIm2col(), ConvWinograd()],
}

LOSSY_KERNELS: Dict[str, List[Kernel]] = {
    "linear": [LinearLowPrecision(), LinearInt8(), LinearInt4()],
}


def registry_for(op_type: str, *, allow_lossy: bool = False) -> List[Kernel]:
    ks = list(KERNEL_REGISTRY.get(op_type, []))
    if allow_lossy:
        ks += LOSSY_KERNELS.get(op_type, [])
    return ks
