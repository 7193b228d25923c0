"""Pluggable solver engines for SODM level solves.

Every level of Algorithm 1 is K independent partition-local ODM duals of
identical size. A :class:`LocalSolver` advances one whole level:

    (xs (K, m, d), ys (K, m), alphas (K, 2m))
        -> (alphas' (K, 2m), sweeps (K,), kkts (K,))

``sweeps`` counts solver iterations (CD sweeps for the scalar engine,
outer Jacobi passes for the block engines); a warm start already within
tol must report 0 so Algorithm 1 line 5's early-stop check keeps working.

Three engines, selected by ``SODMConfig.engine``:

* ``"scalar"`` — exact Gauss-Seidel CD (:func:`repro.core.dual_cd.solve`)
  vmapped over partitions. Faithful to the paper, latency-bound on
  accelerators (a ``fori_loop`` over 2m coordinates per sweep).

* ``"block"`` — pure-jnp block-Gauss-Seidel
  (:func:`repro.core.dual_cd.solve_block`) vmapped over partitions. The
  XLA oracle of the Pallas path; runs anywhere.

* ``"pallas"`` — greedy (Gauss-Southwell) block CD via the *fused* Pallas
  pass kernel (:mod:`repro.kernels.dual_cd_block`): every pass of a level
  is ONE ``pallas_call`` that runs all diagonal-tile sweeps AND the
  cross-tile Gram matvec the line search needs (no separate per-pass XLA
  matmul). When a partition outgrows ``gram_threshold``, the Gram tiles
  are rebuilt on the fly from the raw features for EVERY ``KernelSpec``
  family (rbf / laplacian / poly / linear — see
  :mod:`repro.kernels.gram`), keeping per-level memory O(m·B) instead of
  the O(m²) of a materialized Q. A kernel without a matrix-free lowering
  above the threshold triggers a one-time warning with the memory
  estimate before falling back to a materialized Q — never silently.

A fourth engine name, ``"dsvrg"``, is NOT a level solver: it is the
paper's "when linear kernel is applied" dispatch (Algorithm 2) to the
communication-efficient primal SVRG solver (:mod:`repro.core.dsvrg`).
The dispatch policy lives in the capability-based solver registry
(:func:`repro.api.registry.resolve_auto`); ``sodm.solve``/
``solve_sharded`` consult it BEFORE entering the level loop — explicitly
via ``SODMConfig.engine = "dsvrg"`` (linear kernel required), or
automatically for linear-kernel problems with
M >= ``SODMConfig.dsvrg_threshold`` — and recover the dual alpha from the
primal solution through ``odm.alpha_from_w``, so every dual-alpha consumer
(predict / baselines / benchmarks) reaches it uniformly.
:func:`wants_dsvrg` survives as the legacy boolean form of that policy.

Engines are plain closures so they can be jitted by the caller with
``spec``/``params``/``tol``/``max_sweeps`` static and used unchanged
inside ``shard_map`` bodies.
"""
from __future__ import annotations

import warnings
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core import dual_cd, kernel_fns as kf
from repro.core import odm
from repro.core.odm import ODMParams
from repro.precision import matmul

Array = jax.Array

# level solvers (LocalSolver implementations) vs every SODMConfig.engine
# value — "dsvrg" is a whole-problem dispatch, not a level solver
LEVEL_ENGINES = ("scalar", "block", "pallas")
ENGINES = LEVEL_ENGINES + ("dsvrg",)


def wants_dsvrg(engine: str | None, kernel_name: str, M: int,
                threshold: int) -> bool:
    """The paper's linear-kernel dispatch rule (Section 3.3) — LEGACY
    predicate form.

    The policy itself now lives in the capability-based solver registry
    (:func:`repro.api.registry.resolve_auto`, the single source every
    route resolution goes through); this wrapper keeps the historical
    boolean API: True when the whole solve should route to the DSVRG
    primal engine — explicitly (``engine == "dsvrg"``, linear kernel
    required, raises otherwise) or automatically for a linear-kernel
    problem at/above ``threshold`` instances when the engine is left
    UNSET (``None``); any explicitly named engine, scalar included, is
    honored whatever the problem size.
    """
    from repro.api import registry    # deferred: registry imports core
    return registry.resolve_auto(kernel_name, M, engine=engine,
                                 threshold=threshold).name == "dsvrg"

# kernel names already warned about falling back to a materialized Q
_MATERIALIZED_WARNED: set[str] = set()


def _warn_materialized_fallback(name: str, K: int, m: int,
                                itemsize: int) -> None:
    """One-time warning when a kernel has no matrix-free Gram path.

    After the matrix-free Gram subsystem every ``KernelSpec`` family has
    one, so this only fires for kernels added without a tile lowering —
    but it must never be silent: the fallback allocates the full O(m²)
    Gram per partition.
    """
    if name in _MATERIALIZED_WARNED:
        return
    _MATERIALIZED_WARNED.add(name)
    gib = K * m * m * itemsize / 2 ** 30
    warnings.warn(
        f"kernel {name!r} has no matrix-free Gram lowering; the pallas "
        f"engine is materializing K={K} Gram blocks of {m}x{m} "
        f"(~{gib:.2f} GiB) despite gram_threshold — add the kernel to "
        f"repro.kernels.gram or lower gram_threshold expectations.",
        RuntimeWarning, stacklevel=3)


def _rescale_warm_start(Q: Array, ak: Array, params: ODMParams,
                        m: int) -> tuple[Array, Array]:
    """Exact line search along the warm-start ray (see odm.warm_start_scale).

    SODM merges concatenate child duals solved at scale m_child; this
    rescales them to the parent's scale before the solve. No-op (t = 1)
    for cold starts and already-converged starts. Returns the rescaled
    alpha AND its cache u = Q (zeta - beta) — u is linear in alpha, so
    the matvec paid here is handed to the solver instead of recomputed.
    """
    zeta, beta = odm.split_alpha(ak)
    u = matmul(Q, zeta - beta)
    t = odm.warm_start_scale(u, ak, params, float(m))
    return ak * t, u * t


class LocalSolver(Protocol):
    """Solves all K local ODM duals of one SODM level."""

    def __call__(self, xs: Array, ys: Array, alphas: Array, *,
                 spec: kf.KernelSpec, params: ODMParams, tol: float,
                 max_sweeps: int) -> tuple[Array, Array, Array]:
        ...


# ---------------------------------------------------------------------------
# scalar: exact Gauss-Seidel CD per partition (the paper's Algorithm 1)
# ---------------------------------------------------------------------------

def solve_level_scalar(xs: Array, ys: Array, alphas: Array, *,
                       spec: kf.KernelSpec, params: ODMParams, tol: float,
                       max_sweeps: int) -> tuple[Array, Array, Array]:
    m = xs.shape[1]

    def one(xk, yk, ak):
        Q = kf.signed_gram(spec, xk, yk)
        ak, uk = _rescale_warm_start(Q, ak, params, m)
        res = dual_cd.solve(Q, params, mscale=float(m), alpha0=ak,
                            tol=tol, max_sweeps=max_sweeps, u0=uk)
        return res.alpha, res.sweeps, res.kkt

    return jax.vmap(one)(xs, ys, alphas)


# ---------------------------------------------------------------------------
# block: pure-jnp block-Gauss-Seidel (oracle of the Pallas path)
# ---------------------------------------------------------------------------

def solve_level_block(xs: Array, ys: Array, alphas: Array, *,
                      spec: kf.KernelSpec, params: ODMParams, tol: float,
                      max_sweeps: int,
                      block: int = 256) -> tuple[Array, Array, Array]:
    m = xs.shape[1]
    blk = min(block, m)

    def one(xk, yk, ak):
        Q = kf.signed_gram(spec, xk, yk)
        ak, uk = _rescale_warm_start(Q, ak, params, m)
        res = dual_cd.solve_block(Q, params, mscale=float(m), block=blk,
                                  alpha0=ak, tol=tol, max_outer=max_sweeps,
                                  u0=uk)
        return res.alpha, res.sweeps, res.kkt

    return jax.vmap(one)(xs, ys, alphas)


# ---------------------------------------------------------------------------
# pallas: greedy tile kernel, whole level per pallas_call
# ---------------------------------------------------------------------------

def solve_level_pallas(xs: Array, ys: Array, alphas: Array, *,
                       spec: kf.KernelSpec, params: ODMParams, tol: float,
                       max_sweeps: int, block: int = 256,
                       gram_threshold: int = 4096,
                       adaptive: bool = True) -> tuple[Array, Array, Array]:
    from repro.kernels import dual_cd_block as cdk
    from repro.kernels import gram as gram_mod
    from repro.kernels import ops

    K, m, _ = xs.shape
    B = min(block, m)
    nblk = -(-m // B)
    mp = nblk * B
    pad = mp - m
    valid = (jnp.arange(mp) < m).astype(xs.dtype)

    xp = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    # padded labels are 0 so the signed matvec y ⊙ (K @ (y ⊙ g)) zeroes
    # padded rows and columns without ever masking a Gram tile
    yp = jnp.pad(ys, ((0, 0), (0, pad)))
    z0, b0 = alphas[:, :m], alphas[:, m:]
    a0 = jnp.concatenate([jnp.pad(z0, ((0, 0), (0, pad))),
                          jnp.pad(b0, ((0, 0), (0, pad)))], axis=1)

    matrix_free = (m > gram_threshold
                   and spec.name in gram_mod.MATRIX_FREE_KERNELS)
    if m > gram_threshold and not matrix_free:
        _warn_materialized_fallback(spec.name, K, mp, xs.dtype.itemsize)
    if matrix_free:
        # diagonal Gram tiles only: (K, nblk, B, B) — O(m·B) per partition;
        # the off-diagonal mass is regenerated tile-by-tile inside the
        # fused pass kernel and never materialized
        x_t = xp.reshape(K * nblk, B, -1)
        y_t = yp.reshape(K * nblk, B)
        qb = jax.vmap(lambda xb, yb: kf.signed_gram(spec, xb, yb))(x_t, y_t)
        qb = qb.reshape(K, nblk, B, B)
        src = gram_mod.make_kernel_source(spec, xp, yp, bm=B, bn=B,
                                          interpret=ops._INTERPRET)
    else:
        Qp = jax.vmap(lambda xk, yk: kf.signed_gram(spec, xk, yk))(xp, yp)
        Qp = Qp * (valid[None, :, None] * valid[None, None, :])
        qb = jax.vmap(lambda q: cdk.extract_diag_blocks(q, B))(Qp)
        src = gram_mod.DenseSource(Qp)

    # warm-start ray rescale, batched over partitions; u is linear in
    # alpha so the rescaled cache rides along to the solver for free
    u0 = src.matvec(a0[:, :mp] - a0[:, mp:])
    t = jax.vmap(lambda u, a: odm.warm_start_scale(u, a, params,
                                                   float(m)))(u0, a0)
    a0 = a0 * t[:, None]
    u0 = u0 * t[:, None]

    out, kkts, passes = cdk.solve_level(
        qb, src, a0, c=params.c, ups=params.ups, theta=params.theta,
        mscale=float(m), n_passes=max_sweeps, tol=tol, valid=valid,
        us0=u0, adaptive=adaptive, interpret=ops._INTERPRET)
    alphas = jnp.concatenate([out[:, :m], out[:, mp:mp + m]], axis=1)
    sweeps = jnp.full((K,), passes, jnp.int32)
    return alphas, sweeps, kkts


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def make_local_solver(engine: str | None = "scalar", block: int = 256,
                      gram_threshold: int = 4096,
                      adaptive: bool = True) -> LocalSolver:
    """Resolve an engine name (``SODMConfig.engine``) to a LocalSolver.

    ``None`` (the config default, meaning "auto") resolves to the scalar
    level solver — the auto DSVRG dispatch happens in ``sodm`` *before*
    the level loop, so by the time a LocalSolver is built the choice is
    between level engines only.
    """
    if engine is None:
        engine = "scalar"
    if engine == "scalar":
        return solve_level_scalar
    if engine == "block":
        def _block(xs, ys, alphas, *, spec, params, tol, max_sweeps):
            return solve_level_block(xs, ys, alphas, spec=spec,
                                     params=params, tol=tol,
                                     max_sweeps=max_sweeps, block=block)
        return _block
    if engine == "pallas":
        def _pallas(xs, ys, alphas, *, spec, params, tol, max_sweeps):
            return solve_level_pallas(xs, ys, alphas, spec=spec,
                                      params=params, tol=tol,
                                      max_sweeps=max_sweeps, block=block,
                                      gram_threshold=gram_threshold,
                                      adaptive=adaptive)
        return _pallas
    if engine == "dsvrg":
        raise ValueError(
            "engine='dsvrg' is a whole-problem primal solver, not a level "
            "solver — sodm.solve/solve_sharded dispatch it before the "
            "level loop (see engines.wants_dsvrg)")
    raise ValueError(
        f"engine must be one of {LEVEL_ENGINES} (or 'dsvrg'/None at the "
        f"SODMConfig level), got {engine!r}")
