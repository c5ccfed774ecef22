"""Inputs made on the device from the run's seed, in one jitted call each."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for (seed, stream); any non-negative integer seed, also
    past 32 bits (it is hashed to two 32-bit words first)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


@partial(jax.jit, static_argnames=("n", "d", "low", "high"))
def uniform(key, *, n: int, d: int, low: float, high: float) -> jax.Array:
    """(n, d) float32 uniform in [low, high): the paper's §V-A data."""
    return jax.random.uniform(key, (n, d), jnp.float32, low, high)
