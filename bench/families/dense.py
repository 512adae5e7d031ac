"""The dense decoder family (Llama, Mistral): every block is grouped-query
attention and a gated MLP, the embedding and the output head tied or not.

A family is what the harness knows of one kind of model; the harness
loads ``bench/families/<family>.py`` by the name a configuration file
gives under ``family`` (``modelcfg.py``) and calls, on it alone:

* ``model(c)``: the sizes read from the configuration file's published
  keys ``c``, ``vocab`` among them;
* ``arch_config(m)``: the program's ``ArchConfig`` of model ``m``;
* ``make(seed, m)``: seeded weights in the program's parameter layout,
  made on the device in one jitted call;
* ``prefill(m, seq)``, ``decode_step(m, pos)``: the operations and bytes
  of a first token and of one decode step (``flops.py``'s conventions);
* ``roles(m, mix, peak)``: the executables ``tracing.reduce`` looks for
  in a trace, each with the least time of its runs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import flops
from flops import BF16
from tracing import Role
from weights import NORM_GAIN_STD, seed_key


def model(c: Dict[str, Any]) -> Dict[str, Any]:
    heads = c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "heads": heads,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "tied": c["tie_word_embeddings"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
    }


def arch_config(m: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=m["name"], family="dense", num_layers=m["layers"],
        d_model=m["d_model"], d_ff=m["d_ff"], vocab_size=m["vocab"],
        num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=m["tied"], dtype=m["dtype"])


def make(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter pytree of ``m`` in bfloat16, on the default device:
    blocks stacked on a leading layer axis (``repro.models``' layout).
    Scales follow the usual fan-in initialisation; the norm gains, which
    the program applies as ``1 + g``, are drawn too, so that a norm weight
    lost on the way to the device shows in the comparison."""
    import jax
    import jax.numpy as jnp

    L, d, ff, V = m["layers"], m["d_model"], m["d_ff"], m["vocab"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    dt = jnp.bfloat16

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def build(key):
        ks = iter(jax.random.split(key, 12))
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
                "mlp": {
                    "w_gate": normal(next(ks), (L, d, ff), 1 / math.sqrt(d)),
                    "w_up": normal(next(ks), (L, d, ff), 1 / math.sqrt(d)),
                    "w_down": normal(next(ks), (L, ff, d), 1 / math.sqrt(ff)),
                },
            },
        }
        if not m["tied"]:
            p["lm_head"] = normal(next(ks), (d, V), 1 / math.sqrt(d))
        return p

    return jax.jit(build)(seed_key(seed, 0))


def _block_weights(m: Dict) -> int:
    d, ff = m["d_model"], m["d_ff"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def tblock(m: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One decoder block over a whole prompt (the cold prefill's unit)."""
    H, hd, d = m["heads"], m["head_dim"], m["d_model"]
    ops = (2 * batch * seq * _block_weights(m)
           + 2 * 2 * batch * H * hd * flops.attention_pairs(0, seq))
    nbytes = BF16 * (_block_weights(m) + 2 * d) + 2 * BF16 * batch * seq * d
    return {"flops": float(ops), "bytes": float(nbytes)}


def lm_head(m: Dict, positions: int = 1) -> Dict[str, float]:
    d, V = m["d_model"], m["vocab"]
    return {"flops": float(2 * positions * d * V),
            "bytes": float(BF16 * (d * V + d) + 4 * positions * V)}


def decode_step(m: Dict, pos: int, batch: int = 1) -> Dict[str, float]:
    """One token through every layer and the output projection, attending
    to positions 0..pos."""
    L, H, KV, hd, d = (m["layers"], m["heads"], m["kv_heads"],
                       m["head_dim"], m["d_model"])
    head = lm_head(m, batch)
    ops = (L * (2 * batch * _block_weights(m)
                + 2 * 2 * batch * H * hd * (pos + 1))
           + head["flops"])
    nbytes = (L * (BF16 * (_block_weights(m) + 2 * d)
                   + 2 * BF16 * batch * (pos + 1) * KV * hd)
              + head["bytes"] + BF16 * batch * d)
    return {"flops": float(ops), "bytes": float(nbytes)}


def prefill(m: Dict, seq: int) -> Dict[str, float]:
    """The model work that a first token needs: every block over the
    prompt, and the output projection at the last position."""
    blk, head = tblock(m, seq), lm_head(m, 1)
    return {"flops": m["layers"] * blk["flops"] + head["flops"],
            "bytes": m["layers"] * blk["bytes"] + head["bytes"]}


def roles(m: Dict, mix: Dict, peak: Dict[str, float]) -> List[Role]:
    """Both found by what the algorithm fixes, not by name, so that traces
    recorded before the program named its executables read alike: the
    tblock executable runs once per layer in every prefill, the decode step
    at least once per decoded token after the first two in every decode
    phase, its runs at positions 0, 1, ..."""
    block = flops.least_time(tblock(m, mix["prompt_tokens"]), peak)
    return [
        Role("tblock", "prefill", lambda name, n: n == m["layers"],
             lambda runs: sum(runs) * block),
        Role("decode_step", "decode",
             lambda name, n: n >= mix["new_tokens"] - 2,
             lambda runs: sum(
                 sum(flops.least_time(decode_step(m, p), peak)
                     for p in range(n)) for n in runs)),
    ]
