"""The median-distance bandwidth heuristic, a frozen copy of
``repro.core.kernel_fns.median_gamma``: gamma = 1 / median ||x_i - x_j||^2
over the pairs of the first ``sample`` rows."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("sample",))
def _median_sq_dist(x: jax.Array, sample: int) -> jax.Array:
    xs = x[:sample]
    sq = jnp.sum(xs * xs, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(xs, xs.T,
                                                      precision=HIGHEST)
    d2 = jnp.maximum(d2, 0.0)
    iu = jnp.triu_indices(xs.shape[0], 1)
    return jnp.median(d2[iu])


def median_gamma(x: jax.Array, sample: int = 256) -> float:
    return float(1.0 / jnp.maximum(_median_sq_dist(x, sample), 1e-6))
