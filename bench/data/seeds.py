"""``--seed`` to a JAX PRNG key.

The driver's seeds go a little past 2**31, beyond a signed 32-bit key, so
the low 32 bits make the key and the high bits are folded in.
"""
from __future__ import annotations

import jax


def key(seed: int, stream: int = 0) -> jax.Array:
    """The key of ``seed``; ``stream`` names an independent draw from it."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, stream)
