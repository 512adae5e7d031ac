"""Seeded weights for a dense decoder, made on the device in one jitted call.

The layout is the one the system under test takes (``repro.models``'
parameter pytree: blocks stacked on a leading layer axis); the values are
drawn here, from ``--seed``, so that the reference never uses anything the
program made. Scales follow the usual fan-in initialisation, and the norm
gains, which the program applies as ``1 + g``, are drawn too so that a
norm weight lost on the way to the device shows in the comparison.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

NORM_GAIN_STD = 0.1


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``; any non-negative integer."""
    import jax

    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))


def make(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter pytree of model ``m`` (a ``configs.model`` dict) in
    bfloat16, on the default device."""
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
