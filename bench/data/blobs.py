"""Two anisotropic Gaussian blobs with label noise, features in [0, 1].

A copy of ``repro.data.synthetic.make_blobs`` (the stand-in for the
paper's LIBSVM sets at their published rows, width and class balance),
frozen here so the benchmark's data cannot move with the program. Three
departures, neither of which changes the distribution:

* the whole draw is one jitted program (the op-by-op original compiles
  every operation on its first use, tens of seconds on the chip);
* the rotation product runs at ``HIGHEST`` precision, so the chip and the
  CPU draw the same numbers for a key;
* ``train_fraction`` 1.0 keeps every row for training (the streamed
  cell fits on the whole published set).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rows_kept(rows: int) -> int:
    """Rows the generator draws for a published count (a multiple of 8)."""
    n = max(64, int(rows))
    return n - n % 8


def train_rows(rows: int, train_fraction: float) -> int:
    """Training rows of the split (a multiple of 8, as the original)."""
    n = rows_kept(rows)
    if train_fraction >= 1.0:
        return n
    n_tr = int(n * train_fraction)
    return n_tr - n_tr % 8


@functools.partial(jax.jit, static_argnames=(
    "rows", "features", "balance", "sep", "train_fraction"))
def make(key: jax.Array, *, rows: int, features: int, balance: float,
         sep: float, train_fraction: float = 0.8):
    """(x_train, y_train, x_test, y_test) as float32 device arrays."""
    n = rows_kept(rows)
    d = features
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    n_pos = int(n * balance)
    n_neg = n - n_pos
    # class means along a zero-mean direction: the bias-free linear ODM
    # can only place hyperplanes through the origin, and the [0, 1]
    # normalisation below shifts the midpoint along the all-ones vector
    u = jax.random.normal(k1, (d,))
    u = u - jnp.mean(u)
    u = u / jnp.linalg.norm(u)
    rot = jax.random.normal(k2, (d, d)) / jnp.sqrt(d)
    mix = jnp.eye(d) + 0.3 * rot
    xp = jnp.matmul(jax.random.normal(k3, (n_pos, d)), mix,
                    precision=HIGHEST) + sep * u
    xn = jnp.matmul(jax.random.normal(k4, (n_neg, d)), mix,
                    precision=HIGHEST) - sep * u
    x = jnp.concatenate([xp, xn])
    y = jnp.concatenate([jnp.ones(n_pos), -jnp.ones(n_neg)])
    perm = jax.random.permutation(k5, n)
    x, y = x[perm], y[perm]
    noise = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.02, (n,))
    y = jnp.where(noise, -y, y)
    lo = jnp.min(x, axis=0, keepdims=True)
    hi = jnp.max(x, axis=0, keepdims=True)
    x = (x - lo) / jnp.maximum(hi - lo, 1e-9)
    n_tr = train_rows(rows, train_fraction)
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]
