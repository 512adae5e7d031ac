"""The sparse-expert cold path's parts: the held-expert layer (one chip's
share of an expert-parallel layer, dropless), YaRN's frequencies, and the
cold graph's ``moeblock`` units of two attention kinds, next to dense
graphs whose specs and shape classes stay as they were."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.llm_graph import build_llm_graph_specs, tiny_llm_graph
from repro.core.registry import shape_class_key
from repro.models import layers as L
from repro.models import moe as MOE

D, FF, E, K = 32, 16, 16, 4


def _cfg(held=(), **kw):
    base = get_config("mellum2-12b-a2.5b").reduced(
        d_model=D, d_ff=FF, num_experts=E, top_k=K, dtype="float32")
    return dataclasses.replace(base, held_experts=tuple(held), **kw)


def _layer(seed=0):
    """Router over all E experts and every expert's f32 weights."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": jax.random.normal(ks[0], (D, E)) / math.sqrt(D),
            "w_gate": jax.random.normal(ks[1], (E, D, FF)) / math.sqrt(D),
            "w_up": jax.random.normal(ks[2], (E, D, FF)) / math.sqrt(D),
            "w_down": jax.random.normal(ks[3], (E, FF, D)) / math.sqrt(FF)}


def _reference(p, x, held=range(E)):
    """Token by token, in numpy: the top K of the router's softmax,
    renormalised; the picked experts that are held add p_e * SwiGLU_e."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    out = np.zeros_like(x, np.float64)
    for t, xt in enumerate(np.asarray(x, np.float64)):
        logits = xt @ p["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:K]
        for e in top:
            if e in held:
                g = xt @ p["w_gate"][e]
                h = g / (1 + np.exp(-g)) * (xt @ p["w_up"][e])
                out[t] += probs[e] / probs[top].sum() * (h @ p["w_down"][e])
    return out


def _share(p, held):
    return dict(p, **{k: p[k][jnp.asarray(held)]
                      for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("tokens", [1, 7, 64])
def test_expert_shares_sum_to_the_whole_layer(tokens):
    """Four chips' shares of a 16-expert layer, 4 experts each, add up to
    the uncut reference of the whole layer, and each share to the
    reference given that share."""
    p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, D))
    whole = _reference(p, x)
    total = np.zeros_like(whole)
    for chip in range(4):
        held = list(range(4 * chip, 4 * chip + 4))
        part = np.asarray(MOE.held_experts_apply(_share(p, held), x,
                                                 _cfg(held)))
        np.testing.assert_allclose(part, _reference(p, x, held), atol=1e-5)
        total += part
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_dropless_when_every_token_picks_one_expert():
    """A router rigged to send every token to expert 0 first: the held
    layer gives the reference's output for all 64 tokens, where the
    capacity layer (``_local_moe``, 32 rows an expert here) drops half."""
    p = _layer()
    p["router"] = p["router"].at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, D)))
    want = _reference(p, x)
    cfg = _cfg(range(E))
    got = MOE.held_experts_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    capped, _ = MOE._local_moe(x, p["router"], p["w_gate"], p["w_up"],
                               p["w_down"], dataclasses.replace(
                                   cfg, held_experts=()))
    assert not np.allclose(np.asarray(capped), want, atol=1e-3)


def test_decode_rows_match_the_prefill():
    """One token at a time (decode) and the whole prompt at once give the
    same expert output per token."""
    p = _layer()
    held = [1, 5, 9, 13]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, D))
    cfg = _cfg(held)
    whole = MOE.held_experts_apply(_share(p, held), x, cfg)
    rows = [MOE.held_experts_apply(_share(p, held), x[:, t:t + 1], cfg)
            for t in range(12)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(rows, 1)),
                               np.asarray(whole), atol=1e-6)


def test_yarn_frequencies_match_a_hand_table():
    """Mellum 2's full layers at head_dim 128: correction range [18, 35],
    RoPE's frequencies below it, a sixteenth above, blended in between;
    cos and sin scaled by the attention factor."""
    yarn = get_config("mellum2-12b-a2.5b").rope_yarn
    freq = L.yarn_inv_freq(128, 500000.0, yarn)
    theta = 500000.0
    table = {0: 1.0, 18: theta ** (-36 / 128),
             27: theta ** (-54 / 128) * (1 - 9 / 17 + 9 / 17 / 16),
             35: theta ** (-70 / 128) / 16, 63: theta ** (-126 / 128) / 16}
    for i, want in table.items():
        assert freq[i] == pytest.approx(want, rel=1e-6), i
    x = jnp.ones((1, 3, 1, 128))
    pos = jnp.arange(3)[None]
    got = L.rope(x, pos, theta, yarn)
    ang = 2 * float(freq[40])
    assert float(got[0, 2, 0, 40]) == pytest.approx(
        yarn.attention_factor * (math.cos(ang) - math.sin(ang)), rel=1e-5)
    both = L.rope(x, pos, theta, yarn, use_yarn=jnp.bool_(False))
    np.testing.assert_allclose(np.asarray(both),
                               np.asarray(L.rope(x, pos, theta)), atol=1e-6)


def test_published_mellum_graph_has_28_moeblocks_of_two_kinds():
    """Traced by ``jax.eval_shape`` at the published widths (nothing is
    allocated): 28 ``moeblock`` units, 21 sliding and 7 full, each with
    the router over 64 experts and all 64 held experts; the two kinds
    fall into two shape classes."""
    cfg = get_config("mellum2-12b-a2.5b")
    specs = build_llm_graph_specs(cfg)
    blocks = [s for s in specs if s.op_type == "moeblock"]
    assert len(blocks) == 28 and len(specs) == 30
    kinds = [s.config["kind"] for s in blocks]
    assert kinds.count("local") == 21 and kinds.count("attn") == 7
    assert kinds[:4] == ["local", "local", "local", "attn"]
    w = blocks[0].weight_shapes
    assert w["router"] == (2304, 64)
    assert w["expert_w_gate"] == (64, 2304, 896)
    assert w["expert_w_down"] == (64, 896, 2304)
    assert w["wq"] == (2304, 4096) and w["wk"] == (2304, 512)
    assert specs[-1].weight_shapes["w"] == (2304, 98304)
    keys = {k: {shape_class_key(s) for s in blocks if s.config["kind"] == k}
            for k in ("local", "attn")}
    assert len(keys["local"]) == len(keys["attn"]) == 1
    assert keys["local"] != keys["attn"]


# shape-class keys of dense graphs as stored before sparse-expert models
# joined the cold path: profile DBs and executable caches written then
# still hit
DENSE_KEYS = {
    "smollm-360m": ["1540543b194b28846bed", "fb67133801524156e124"],
    "mistral-nemo-12b": ["22b6aba762aebd56a95f", "5544702f90ddee82deaa"],
}
TINY_KEYS = ["2714fdf4d34fcf626791", "779bdfa99b33e4cfe057"]


@pytest.mark.parametrize("arch", sorted(DENSE_KEYS))
def test_dense_specs_and_shape_classes_are_unchanged(arch):
    cfg = get_config(arch)
    specs = build_llm_graph_specs(cfg)
    assert [s.op_type for s in specs] == (["embed"]
                                          + ["tblock"] * cfg.num_layers
                                          + ["lmhead"])
    assert all(s.config == {"cfg": cfg} for s in specs)
    assert sorted(specs[1].weight_shapes) == [
        "ln1", "ln2", "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    got = [shape_class_key(s, input_shape=(1, 128, cfg.d_model),
                           input_dtype="bfloat16") for s in specs[:2]]
    assert got == DENSE_KEYS[arch]
    graph, _ = tiny_llm_graph(4)
    assert [shape_class_key(l.spec) for l in graph[:2]] == TINY_KEYS
