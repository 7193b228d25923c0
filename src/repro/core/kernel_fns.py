"""Kernel functions for ODM / SODM.

Everything is pure jnp and jit-safe. Kernels are exposed both as
``KernelSpec`` (a small pytree-friendly description that can be threaded
through shard_map'd code) and as plain functions.

The Gram computation is the nonlinear-kernel hot spot of the paper; the
tiled matrix-free Pallas lowering of every family here lives in
``repro.kernels.gram`` and is validated against these pure-jnp grams.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.precision import matmul

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Static description of a positive-definite kernel.

    Attributes:
      name:  one of 'linear' | 'rbf' | 'laplacian' | 'poly'.
      gamma: bandwidth for rbf/laplacian, scale for poly.
      degree: polynomial degree (poly only).
      coef0: polynomial offset (poly only).
    """

    name: str = "rbf"
    gamma: float = 1.0
    degree: int = 3
    coef0: float = 1.0

    def is_shift_invariant(self) -> bool:
        return self.name in ("rbf", "laplacian")

    def family(self) -> str:
        """Accumulation family of the matrix-free Gram lowering.

        ``"l2"`` kernels (rbf/poly/linear) build their tiles from the
        ``x @ z.T`` cross term on the MXU; ``"l1"`` kernels (laplacian)
        need a tiled L1 reduction on the VPU (no matmul form exists).
        Delegates to :mod:`repro.kernels.gram` (the lowering itself) so
        there is exactly one registry of the split.
        """
        from repro.kernels import gram  # deferred: core must stay
        #                                 importable without kernels
        if self.name in gram.L1_KERNELS:
            return "l1"
        if self.name in gram.MATRIX_FREE_KERNELS:
            return "l2"
        raise ValueError(f"no matrix-free lowering for {self.name!r}")

    def diag_value(self) -> float:
        """kappa(x, x) for shift-invariant kernels (the r^2 of Theorem 2)."""
        if self.name in ("rbf", "laplacian"):
            return 1.0
        raise ValueError(f"diag_value undefined for kernel {self.name!r}")


# ---------------------------------------------------------------------------
# pairwise distances / inner products
# ---------------------------------------------------------------------------

def sq_dists(x: Array, z: Array) -> Array:
    """Pairwise squared euclidean distances, (m, n) for x:(m,d), z:(n,d).

    Uses the expanded form so the cross term is a single matmul (MXU-bound
    on TPU); clamps tiny negatives introduced by cancellation.
    """
    xx = jnp.sum(x * x, axis=-1)[:, None]
    zz = jnp.sum(z * z, axis=-1)[None, :]
    cross = matmul(x, z.T)
    return jnp.maximum(xx + zz - 2.0 * cross, 0.0)


def l1_dists(x: Array, z: Array) -> Array:
    """Pairwise L1 distances (m, n). O(m n d) memory-bound; used by laplacian."""
    return jnp.sum(jnp.abs(x[:, None, :] - z[None, :, :]), axis=-1)


# ---------------------------------------------------------------------------
# gram matrices
# ---------------------------------------------------------------------------

def linear_gram(x: Array, z: Array) -> Array:
    return matmul(x, z.T)


def rbf_gram(x: Array, z: Array, gamma: float) -> Array:
    return jnp.exp(-gamma * sq_dists(x, z))


def laplacian_gram(x: Array, z: Array, gamma: float) -> Array:
    return jnp.exp(-gamma * l1_dists(x, z))


def poly_gram(x: Array, z: Array, gamma: float, degree: int, coef0: float) -> Array:
    return (gamma * matmul(x, z.T) + coef0) ** degree


def gram(spec: KernelSpec, x: Array, z: Array | None = None) -> Array:
    """Gram matrix K[i, j] = kappa(x_i, z_j). z defaults to x."""
    z = x if z is None else z
    if spec.name == "linear":
        return linear_gram(x, z)
    if spec.name == "rbf":
        return rbf_gram(x, z, spec.gamma)
    if spec.name == "laplacian":
        return laplacian_gram(x, z, spec.gamma)
    if spec.name == "poly":
        return poly_gram(x, z, spec.gamma, spec.degree, spec.coef0)
    raise ValueError(f"unknown kernel {spec.name!r}")


def gram_diag(spec: KernelSpec, x: Array) -> Array:
    """diag(K(x, x)) without forming the full gram."""
    if spec.name == "linear":
        return jnp.sum(x * x, axis=-1)
    if spec.name in ("rbf", "laplacian"):
        return jnp.ones(x.shape[0], x.dtype)
    if spec.name == "poly":
        return (spec.gamma * jnp.sum(x * x, axis=-1) + spec.coef0) ** spec.degree
    raise ValueError(f"unknown kernel {spec.name!r}")


def signed_gram(spec: KernelSpec, x: Array, y: Array,
                xz: Array | None = None, yz: Array | None = None) -> Array:
    """Q[i, j] = y_i y_j kappa(x_i, z_j) — the ODM dual Hessian block."""
    xz = x if xz is None else xz
    yz = y if yz is None else yz
    return (y[:, None] * yz[None, :]) * gram(spec, x, xz)


def kernel_fn(spec: KernelSpec) -> Callable[[Array, Array], Array]:
    """Returns a closed-over gram function (for APIs wanting a callable)."""
    return partial(gram, spec)


def median_gamma(x: Array, sample: int = 256) -> float:
    """Median-distance heuristic: gamma = 1 / median(||x_i - x_j||^2).

    The standard bandwidth rule for RBF kernels on normalized data; used
    by the benchmark harnesses so one setting works across the paper's
    eight data sets.
    """
    xs = x[:sample]
    d2 = sq_dists(xs, xs)
    iu = jnp.triu_indices(xs.shape[0], 1)
    med = jnp.median(d2[iu])
    return float(1.0 / jnp.maximum(med, 1e-6))


# Registry used by configs / CLI flags.
KERNELS = ("linear", "rbf", "laplacian", "poly")


def make_spec(name: str, gamma: float = 1.0, degree: int = 3,
              coef0: float = 1.0) -> KernelSpec:
    if name not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {name!r}")
    return KernelSpec(name=name, gamma=gamma, degree=degree, coef0=coef0)
