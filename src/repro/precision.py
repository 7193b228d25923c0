"""Matmul precision of every ODM contraction, in one place.

The dual solvers' exact line search and KKT test read the products
``Q @ g`` as f32 values, and served scores are checked against f32
oracles. On TPU a dot at default precision rounds its f32 operands to
bf16 (one MXU pass); ``HIGHEST`` keeps f32 (several passes). The CPU
computes f32 either way, so this choice changes nothing there.

Every contraction of the solvers (``repro.core``), the Pallas kernels and
their references (``repro.kernels``) and the serving paths
(``repro.serve``) passes :data:`MATMUL` explicitly — no caller needs a
``jax.default_matmul_precision`` context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MATMUL = jax.lax.Precision.HIGHEST


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at :data:`MATMUL` precision."""
    return jnp.matmul(a, b, precision=MATMUL)
