"""The sparse-expert decoder family (Mellum 2): every block is grouped-query
attention and an expert layer, a softmax router over all the experts that
picks the top ``k``, renormalised (``norm_topk_prob``; the program takes
no other), and SwiGLU experts; the blocks' attention kinds follow ``layer_types``, sliding-window
layers with plain RoPE and full layers with their own RoPE (YaRN).

The configuration holds one chip's share of an expert-parallel deployment:
``num_experts`` counts the experts held here, ``published.num_experts``
those the router spans. This chip is the deployment's first: it holds
experts ``0 .. num_experts - 1``. The six functions are those of
``families/dense.py``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import flops
from flops import BF16
from tracing import Role
from weights import NORM_GAIN_STD, seed_key

KINDS = {"sliding_attention": "local", "full_attention": "full"}


def model(c: Dict[str, Any]) -> Dict[str, Any]:
    layers = c["num_hidden_layers"]
    if any(t != "sparse" for t in c["mlp_layer_types"][:layers]):
        raise ValueError("every block of this family is sparse")
    if not c["norm_topk_prob"]:
        raise ValueError("the program renormalises the picked experts")
    kinds = [KINDS[t] for t in c["layer_types"][:layers]]
    rope = {KINDS[t]: dict(p) for t, p in c["rope_parameters"].items()}
    return {
        "layers": layers,
        "kinds": kinds,
        "d_model": c["hidden_size"],
        "d_ff": c["moe_intermediate_size"],
        "vocab": c["vocab_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"],
        "tied": c["tie_word_embeddings"],
        "norm_eps": float(c["rms_norm_eps"]),
        "experts": c["published"]["num_experts"],
        "held": list(range(c["num_experts"])),
        "top_k": c["num_experts_per_tok"],
        "norm_topk": c["norm_topk_prob"],
        "window": c["sliding_window"],
        "rope": rope,
    }


def arch_config(m: Dict[str, Any]):
    from repro.configs.base import ArchConfig, Yarn

    local, full = m["rope"]["local"], m["rope"]["full"]
    if local["rope_type"] != "default" or \
            local["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program takes plain RoPE on the sliding "
                         "layers, at the full layers' theta")
    yarn = None
    if full["rope_type"] == "yarn":
        yarn = Yarn(factor=float(full["factor"]),
                    original_max_positions=int(
                        full["original_max_position_embeddings"]),
                    beta_fast=float(full["beta_fast"]),
                    beta_slow=float(full["beta_slow"]),
                    attention_factor=float(full["attention_factor"]))
    elif full["rope_type"] != "default":
        raise ValueError(f"RoPE type {full['rope_type']!r}")
    return ArchConfig(
        name=m["name"], family="moe", num_layers=m["layers"],
        d_model=m["d_model"], d_ff=m["d_ff"], vocab_size=m["vocab"],
        num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], rope_theta=float(full["rope_theta"]),
        rope_yarn=yarn, sliding_window=m["window"],
        layer_pattern=tuple("local" if k == "local" else "attn"
                            for k in m["kinds"]),
        num_experts=m["experts"], top_k=m["top_k"],
        held_experts=tuple(m["held"]), norm_eps=m["norm_eps"], tie_embeddings=m["tied"], dtype=m["dtype"])


def make(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter pytree of ``m`` in bfloat16, on the default device:
    blocks stacked on a leading layer axis, the router over every expert
    (``moe.router``, (L, d, experts)) and the held experts stacked in
    ``held`` order (``moe.w_gate``, (L, held, d, d_ff), ...). Norm gains
    are drawn too, as in ``families/dense.py``."""
    import jax
    import jax.numpy as jnp

    L, d, ff, V = m["layers"], m["d_model"], m["d_ff"], m["vocab"]
    E, H = m["experts"], len(m["held"])
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    dt = jnp.bfloat16

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def build(key):
        ks = iter(jax.random.split(key, 14))
        p = {
            "embed": normal(next(ks), (V, d), 0.02),
            "final_norm": normal(next(ks), (d,), NORM_GAIN_STD),
            "blocks": {
                "ln1": normal(next(ks), (L, d), NORM_GAIN_STD),
                "ln2": normal(next(ks), (L, d), NORM_GAIN_STD),
                "attn": {
                    "wq": normal(next(ks), (L, d, q), 1 / math.sqrt(d)),
                    "wk": normal(next(ks), (L, d, kv), 1 / math.sqrt(d)),
                    "wv": normal(next(ks), (L, d, kv), 1 / math.sqrt(d)),
                    "wo": normal(next(ks), (L, q, d), 1 / math.sqrt(q)),
                },
                "moe": {
                    "router": normal(next(ks), (L, d, E), 1 / math.sqrt(d)),
                    "w_gate": normal(next(ks), (L, H, d, ff),
                                     1 / math.sqrt(d)),
                    "w_up": normal(next(ks), (L, H, d, ff), 1 / math.sqrt(d)),
                    "w_down": normal(next(ks), (L, H, ff, d),
                                     1 / math.sqrt(ff)),
                },
            },
        }
        if not m["tied"]:
            p["lm_head"] = normal(next(ks), (d, V), 1 / math.sqrt(d))
        return p

    return jax.jit(build)(seed_key(seed, 0))


def _attn_weights(m: Dict) -> int:
    d = m["d_model"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d


def _expert_weights(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def _held_hit(m: Dict, tokens: int) -> float:
    """Expected held experts that at least one of ``tokens`` routes to."""
    return len(m["held"]) * (1 - (1 - m["top_k"] / m["experts"]) ** tokens)


def _routed(m: Dict, tokens: int) -> float:
    """Expected (token, held expert) pairs the router picks."""
    return tokens * m["top_k"] * len(m["held"]) / m["experts"]


def moeblock(m: Dict, seq: int, kind: str = "local",
             batch: int = 1) -> Dict[str, float]:
    """One sparse-expert block over a whole prompt (the cold prefill's
    unit): its attention, router and the routed tokens' held experts;
    every held expert that a token picks is read once."""
    H, hd, d = m["heads"], m["head_dim"], m["d_model"]
    tokens = batch * seq
    pairs = flops.attention_pairs(0, seq)
    if kind == "local":
        pairs -= flops.attention_pairs(0, max(seq - m["window"], 0))
    ops = (2 * tokens * (_attn_weights(m) + d * m["experts"])
           + 2 * _routed(m, tokens) * _expert_weights(m)
           + 2 * 2 * batch * H * hd * pairs)
    nbytes = (BF16 * (_attn_weights(m) + d * m["experts"] + 2 * d
                      + _held_hit(m, tokens) * _expert_weights(m))
              + 2 * BF16 * tokens * d)
    return {"flops": float(ops), "bytes": float(nbytes)}


def lm_head(m: Dict, positions: int = 1) -> Dict[str, float]:
    d, V = m["d_model"], m["vocab"]
    return {"flops": float(2 * positions * d * V),
            "bytes": float(BF16 * (d * V + d) + 4 * positions * V)}


def decode_step(m: Dict, pos: int, batch: int = 1) -> Dict[str, float]:
    """One token through every layer and the output projection, at the
    expected routing: ``top_k * held / experts`` held experts a layer
    (one, at 8 of 64 held and top 8); a sliding layer attends to the last
    ``window`` positions, a full layer to positions 0..pos."""
    H, KV, hd, d = m["heads"], m["kv_heads"], m["head_dim"], m["d_model"]
    head = lm_head(m, batch)
    ops, nbytes = head["flops"], head["bytes"] + BF16 * batch * d
    for kind in m["kinds"]:
        keys = min(pos + 1, m["window"]) if kind == "local" else pos + 1
        ops += (2 * batch * (_attn_weights(m) + d * m["experts"])
                + 2 * _routed(m, batch) * _expert_weights(m)
                + 2 * 2 * batch * H * hd * keys)
        nbytes += (BF16 * (_attn_weights(m) + d * m["experts"] + 2 * d
                           + _held_hit(m, batch) * _expert_weights(m))
                   + 2 * BF16 * batch * keys * KV * hd)
    return {"flops": float(ops), "bytes": float(nbytes)}


def prefill(m: Dict, seq: int) -> Dict[str, float]:
    """The model work that a first token needs: every block over the
    prompt, and the output projection at the last position."""
    blocks = [moeblock(m, seq, kind) for kind in m["kinds"]]
    head = lm_head(m, 1)
    return {"flops": sum(b["flops"] for b in blocks) + head["flops"],
            "bytes": sum(b["bytes"] for b in blocks) + head["bytes"]}


def roles(m: Dict, mix: Dict, peak: Dict[str, float]) -> List[Role]:
    """The blocks of each attention kind compile to an executable of
    their own, ``jit_moeblock_<kernel>``, that runs once per layer of its
    kind in every prefill; the decode step, ``jit_decode_step``, at least
    once per decoded token after the first two in every decode phase, its
    runs at positions 0, 1, ..."""
    seq = mix["prompt_tokens"]
    out = []
    for kind in ("local", "full"):
        n = m["kinds"].count(kind)
        block = flops.least_time(moeblock(m, seq, kind), peak)
        out.append(Role(
            f"moeblock_{kind}", "prefill",
            lambda name, runs, n=n: (name.startswith("jit_moeblock_")
                                     and runs == n),
            lambda runs, block=block: sum(runs) * block))
    out.append(Role(
        "moe_decode_step", "decode",
        lambda name, n: (name.startswith("jit_decode_step")
                         and n >= mix["new_tokens"] - 2),
        lambda runs: sum(sum(flops.least_time(decode_step(m, p), peak)
                             for p in range(n)) for n in runs)))
    return out
