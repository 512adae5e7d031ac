"""Compile the cold-LLM main path's programs for a TPU v5e chip.

Nothing runs: each program is lowered against shapes placed on a *described*
v5e device and compiled by the TPU compiler that ships with ``libtpu``. That
catches what interpret mode and the CPU backend cannot — Mosaic refusing a
Pallas kernel, a program that does not fit, a layout the chip cannot take —
at no chip time. Shapes are smollm-360m's published widths, and
Mellum2-12B-A2.5B's for the sparse-expert blocks and their decode step.

The topology is described inside a module-scoped fixture (never at import
time): only one process may load the TPU library, and only the test worker
that runs this file should.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CFG = get_config("smollm-360m")
PROMPT = 64


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Executables for a described chip are written to JAX's persistent
    cache but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("kernel", ["bf16_cast", "f32_direct"])
def test_tblock_execute_compiles(one_chip, kernel):
    from repro.core.llm_graph import build_llm_graph_specs

    spec = build_llm_graph_specs(CFG)[1]
    assert spec.op_type == "tblock"
    kern = _kernel("tblock", kernel)
    dt = jnp.bfloat16 if kernel == "bf16_cast" else jnp.float32
    w = {k: _sds(s, dt, one_chip) for k, s in spec.weight_shapes.items()}
    x = _sds((1, PROMPT, CFG.d_model), jnp.bfloat16, one_chip)
    _compile(lambda w, x: kern.execute(w, x, spec), w, x)


@pytest.mark.parametrize("kernel", ["bf16_cast", "direct"])
def test_lm_head_compiles(one_chip, kernel):
    from repro.core.llm_graph import build_llm_graph_specs

    spec = build_llm_graph_specs(CFG)[-1]
    assert spec.weight_shapes["w"] == (CFG.d_model, CFG.vocab_size)
    kern = _kernel("lmhead", kernel)
    dt = jnp.bfloat16 if kernel == "bf16_cast" else jnp.float32
    w = {k: _sds(s, dt, one_chip) for k, s in spec.weight_shapes.items()}
    x = _sds((1, PROMPT, CFG.d_model), jnp.bfloat16, one_chip)
    out = _compile(lambda w, x: kern.execute(w, x, spec), w, x)
    assert out.out_info.shape == (1, PROMPT, CFG.vocab_size)


def test_decode_step_compiles(one_chip):
    from repro.models import transformer as T

    cfg = CFG.reduced(num_layers=2, d_model=CFG.d_model, d_ff=CFG.d_ff,
                      vocab_size=CFG.vocab_size, num_heads=CFG.num_heads,
                      num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim)
    place = lambda a: _sds(a.shape, a.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(place, jax.eval_shape(
        lambda: T.init_decode_state(cfg, 1, PROMPT + 16)))
    batch = {"tokens": _sds((1, 1), jnp.int32, one_chip)}
    pos = _sds((), jnp.int32, one_chip)
    _compile(lambda p, s, b, i: T.decode_step(p, s, b, i, cfg),
             params, state, batch, pos)


def _mellum_ep8():
    """Mellum 2 at its published widths as one chip of eight holds it:
    the router over 64 experts, experts 0-7 held."""
    import dataclasses

    return dataclasses.replace(get_config("mellum2-12b-a2.5b"),
                               held_experts=tuple(range(8)))


@pytest.mark.parametrize("kernel", ["bf16_cast", "f32_direct"])
@pytest.mark.parametrize("kind", ["local", "attn"])
def test_moeblock_execute_compiles(one_chip, kind, kernel):
    from repro.core.llm_graph import build_llm_graph_specs

    cfg = _mellum_ep8()
    spec = next(s for s in build_llm_graph_specs(cfg)
                if s.op_type == "moeblock" and s.config["kind"] == kind)
    assert spec.weight_shapes["expert_w_up"] == (8, 2304, 896)
    kern = _kernel("moeblock", kernel)
    dt = jnp.bfloat16 if kernel == "bf16_cast" else jnp.float32
    w = {k: _sds(s, dt, one_chip) for k, s in spec.weight_shapes.items()}
    x = _sds((1, 128, cfg.d_model), jnp.bfloat16, one_chip)
    out = _compile(lambda w, x: kern.execute(w, x, spec), w, x)
    assert out.out_info.shape == (1, 128, cfg.d_model)


def test_moe_decode_step_compiles(one_chip):
    """Two periods' worth of kinds scanned as data: one sliding and one
    full layer at the published widths, the whole vocabulary."""
    from repro.models import transformer as T

    cfg = _mellum_ep8().reduced(
        num_layers=4, d_model=2304, d_ff=896, vocab_size=98304,
        num_heads=32, num_kv_heads=4, head_dim=128, num_experts=64,
        top_k=8, sliding_window=1024)
    assert cfg.layer_kinds() == ("local", "local", "local", "attn")
    place = lambda a: _sds(a.shape, a.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(place, jax.eval_shape(
        lambda: T.init_decode_state(cfg, 1, 128 + 34)))
    batch = {"tokens": _sds((1, 1), jnp.int32, one_chip)}
    pos = _sds((), jnp.int32, one_chip)
    _compile(lambda p, s, b, i: T.decode_step(p, s, b, i, cfg),
             params, state, batch, pos)


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_dequant_matmul_compiles(one_chip, bits):
    from repro.kernels import quant as kq

    K, N = CFG.d_model, CFG.d_ff
    x = _sds((PROMPT, K), jnp.float32, one_chip)
    scale = _sds((1, N), jnp.float32, one_chip)
    if bits == 8:
        q = _sds((K, N), jnp.int8, one_chip)
        out = _compile(kq.matmul_dequant_int8, x, q, scale)
    else:
        q = _sds(((K + 1) // 2, N), jnp.uint8, one_chip)
        out = _compile(lambda x, q, s: kq.matmul_dequant_int4(x, q, s, K),
                       x, q, scale)
    assert "tpu_custom_call" in out.as_text()


def _kernel(op_type, name):
    import repro.core.llm_graph  # noqa: F401  (registers the LLM kernels)
    from repro.core.registry import KERNEL_REGISTRY

    return next(k for k in KERNEL_REGISTRY[op_type] if k.name == name)
