"""What every model family's seeded weights share (``make`` in
``bench/families/<family>.py``).

A family makes its parameter pytree in the layout the system under test
takes, on the device in one jitted call, from ``seed_key(seed, 0)``: the
values are drawn here, from ``--seed``, so that the reference never uses
anything the program made.
"""
from __future__ import annotations

import numpy as np

# the program applies a norm gain g as 1 + g; gains are drawn with this
# spread, so that a norm weight lost on the way to the device shows
NORM_GAIN_STD = 0.1


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``; any non-negative integer."""
    import jax

    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))
