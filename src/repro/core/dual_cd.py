"""Dual coordinate descent for the ODM box-constrained QP (Eqn. 3).

The univariate subproblem for coordinate i has the closed form

    alpha_i <- max(alpha_i - grad_i / H_ii, 0)

We maintain the cache ``u = Q (zeta - beta)`` so each coordinate update is
O(m) (one row of Q) instead of O(m^2). Two execution styles are provided:

* :func:`solve` — epoch-based ``lax.while_loop`` over full sweeps; each
  sweep is a ``fori_loop`` over the 2m coordinates (exact Gauss-Seidel).
  This is the faithful reference solver used by SODM level solves on CPU
  and inside shard_map per-partition.

* :func:`solve_block` — block-Gauss-Seidel: exact CD *within* a tile that
  fits VMEM, Jacobi across tiles. This mirrors the Pallas kernel in
  ``repro.kernels.dual_cd_block`` and is its pure-jnp oracle.

Both operate on a *precomputed* Gram matrix Q (signed: Q_ij = y_i y_j k_ij).
For problems too large to materialize Q, SODM never needs to — it only ever
solves partition-sized subproblems (that is the point of the paper).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.odm import ODMParams, dual_grad_from_u, dual_objective, split_alpha
from repro.precision import matmul

Array = jax.Array


class CDResult(NamedTuple):
    alpha: Array        # (2m,) final dual variables
    u: Array            # (m,) final cache Q (zeta - beta)
    sweeps: Array       # () int32 number of sweeps executed
    kkt: Array          # () final projected-gradient infinity norm


def _coord_update(i, state, Q, q_diag, params: ODMParams, mscale):
    """One exact CD step on coordinate i (i < m: zeta_i; else beta_{i-m})."""
    alpha, u = state
    m = Q.shape[0]
    is_zeta = i < m
    row = i - jnp.where(is_zeta, 0, m)          # index into [m]
    # gradient of coordinate i given the cache u
    g_zeta = u[row] + mscale * params.c * params.ups * alpha[i] + (params.theta - 1.0)
    g_beta = -u[row] + mscale * params.c * alpha[i] + (params.theta + 1.0)
    g = jnp.where(is_zeta, g_zeta, g_beta)
    h_zeta = q_diag[row] + mscale * params.c * params.ups
    h_beta = q_diag[row] + mscale * params.c
    h = jnp.where(is_zeta, h_zeta, h_beta)
    new = jnp.maximum(alpha[i] - g / h, 0.0)
    delta = new - alpha[i]
    # u tracks Q (zeta - beta): zeta moves add +delta * Q[:, row], beta -delta
    sign = jnp.where(is_zeta, 1.0, -1.0)
    u = u + (sign * delta) * Q[:, row]
    alpha = alpha.at[i].set(new)
    return alpha, u


def sweep(Q: Array, q_diag: Array, alpha: Array, u: Array,
          params: ODMParams, mscale: float) -> tuple[Array, Array]:
    """One full Gauss-Seidel sweep over all 2m coordinates."""
    m = Q.shape[0]

    def body(i, st):
        return _coord_update(i, st, Q, q_diag, params, mscale)

    return jax.lax.fori_loop(0, 2 * m, body, (alpha, u))


def kkt_from_u(u: Array, alpha: Array, params: ODMParams, mscale: float) -> Array:
    g = dual_grad_from_u(u, alpha, params, mscale)
    proj = jnp.where(alpha > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
    return jnp.max(proj)


def solve(Q: Array, params: ODMParams, mscale: float,
          alpha0: Array | None = None, tol: float = 1e-5,
          max_sweeps: int = 200, u0: Array | None = None) -> CDResult:
    """Run CD sweeps until the projected KKT residual drops below tol.

    ``alpha0`` is the warm start (SODM Algorithm 1 line 12 concatenates the
    child solutions here); defaults to zeros. ``u0`` is the optional
    precomputed cache Q (zeta0 - beta0) — u is linear in alpha, so callers
    that already paid the matvec (e.g. a warm-start rescale) pass the
    scaled cache and skip recomputing it.
    """
    m = Q.shape[0]
    q_diag = jnp.diagonal(Q)
    alpha = jnp.zeros(2 * m, Q.dtype) if alpha0 is None else alpha0
    if u0 is None:
        zeta, beta = split_alpha(alpha)
        u = matmul(Q, zeta - beta)
    else:
        u = u0

    def cond(carry):
        alpha, u, s, kkt = carry
        return jnp.logical_and(s < max_sweeps, kkt > tol)

    def body(carry):
        alpha, u, s, _ = carry
        alpha, u = sweep(Q, q_diag, alpha, u, params, mscale)
        return alpha, u, s + 1, kkt_from_u(u, alpha, params, mscale)

    # evaluate KKT at the warm start so an already-optimal init runs zero
    # sweeps (Algorithm 1 line 5's convergence check reads this)
    init = (alpha, u, jnp.int32(0), kkt_from_u(u, alpha, params, mscale))
    alpha, u, s, kkt = jax.lax.while_loop(cond, body, init)
    return CDResult(alpha=alpha, u=u, sweeps=s, kkt=kkt)


# ---------------------------------------------------------------------------
# block-Gauss-Seidel variant (oracle for the Pallas kernel)
# ---------------------------------------------------------------------------

def solve_block(Q: Array, params: ODMParams, mscale: float,
                block: int = 256, alpha0: Array | None = None,
                tol: float = 1e-5, max_outer: int = 200,
                u0: Array | None = None) -> CDResult:
    """Exact CD within each (block,)-sized tile, Jacobi across tiles.

    The per-tile solve only touches the diagonal Gram block (resident in
    VMEM on TPU); cross-tile coupling enters through the cache u, which is
    refreshed once per outer iteration (one Q @ gamma matmul — MXU work).
    Each Jacobi pass is safeguarded by an exact line search along the
    joint step (f is quadratic along it, and u moves linearly, so the
    optimal damping costs no extra matvec): undamped simultaneous tile
    solves can diverge when the off-diagonal mass beats the M·c·I shift
    (e.g. small c = weak regularization), while the damped pass is
    monotone for any Q.
    """
    m = Q.shape[0]
    nblk = -(-m // block)
    mp = nblk * block
    # zero-pad to a multiple of the block size; padded rows have Q=0, and a
    # padded coordinate's update is max(0 - (theta-1)/h, 0) > 0 for zeta...
    # so mask them explicitly instead.
    pad = mp - m
    Qp = jnp.pad(Q, ((0, pad), (0, pad)))
    q_diag = jnp.diagonal(Qp)
    valid = jnp.arange(mp) < m

    alpha = jnp.zeros(2 * mp, Q.dtype)
    if alpha0 is not None:
        z0, b0 = split_alpha(alpha0)
        alpha = alpha.at[:m].set(z0).at[mp:mp + m].set(b0)

    def tile_solve(qblk, dblk, ablk, ublk, vblk):
        """Exact Gauss-Seidel inside one tile: ablk (2*block,), ublk (block,)."""
        def body(i, st):
            a, u = st
            is_zeta = i < block
            row = i - jnp.where(is_zeta, 0, block)
            gz = u[row] + mscale * params.c * params.ups * a[i] + (params.theta - 1.0)
            gb = -u[row] + mscale * params.c * a[i] + (params.theta + 1.0)
            g = jnp.where(is_zeta, gz, gb)
            hz = dblk[row] + mscale * params.c * params.ups
            hb = dblk[row] + mscale * params.c
            h = jnp.where(is_zeta, hz, hb)
            new = jnp.maximum(a[i] - g / h, 0.0)
            new = jnp.where(vblk[row], new, 0.0)
            delta = new - a[i]
            sign = jnp.where(is_zeta, 1.0, -1.0)
            u = u + (sign * delta) * qblk[:, row]
            return a.at[i].set(new), u
        ablk, _ = jax.lax.fori_loop(0, 2 * block, body, (ablk, ublk))
        return ablk

    def outer(carry):
        alpha, u, it, kkt = carry
        zeta, beta = alpha[:mp], alpha[mp:]
        # process all tiles (Jacobi across tiles, each uses the same u snapshot
        # but exact updates within the tile via the diag block)
        def tile_body(b, acc):
            z, bta = acc
            idx = b * block
            qblk = jax.lax.dynamic_slice(
                Qp, (idx, idx), (block, block))
            dblk = jax.lax.dynamic_slice(q_diag, (idx,), (block,))
            vblk = jax.lax.dynamic_slice(valid, (idx,), (block,))
            zblk = jax.lax.dynamic_slice(z, (idx,), (block,))
            bblk = jax.lax.dynamic_slice(bta, (idx,), (block,))
            ublk = jax.lax.dynamic_slice(u, (idx,), (block,))
            # ublk = external contribution + in-tile contribution; the
            # external part is frozen for this tile solve (Jacobi across
            # tiles) and the in-tile part is tracked incrementally by
            # tile_solve's rank-1 updates, so ublk is the right init.
            ablk = jnp.concatenate([zblk, bblk])
            ablk = tile_solve(qblk, dblk, ablk, ublk, vblk)
            z = jax.lax.dynamic_update_slice(z, ablk[:block], (idx,))
            bta = jax.lax.dynamic_update_slice(bta, ablk[block:], (idx,))
            return z, bta
        z_new, b_new = jax.lax.fori_loop(0, nblk, tile_body, (zeta, beta))
        # exact line search along the joint Jacobi step: f(alpha + t*d) is
        # quadratic in t and u moves linearly, so the optimal damping is
        # closed-form and reuses the one matvec this pass needs anyway.
        # t = 1 when tiles don't conflict; t < 1 tames off-diagonal mass
        # that would otherwise make simultaneous tile updates diverge.
        dz, db = z_new - zeta, b_new - beta
        u_d = matmul(Qp, dz - db)
        gz = u + mscale * params.c * params.ups * zeta + (params.theta - 1.0)
        gb = -u + mscale * params.c * beta + (params.theta + 1.0)
        gdot = matmul(gz, dz) + matmul(gb, db)
        quad = matmul(dz - db, u_d) + mscale * params.c * (
            params.ups * matmul(dz, dz) + matmul(db, db))
        t = jnp.where(quad > 0.0,
                      jnp.clip(-gdot / jnp.maximum(quad, 1e-30), 0.0, 1.0),
                      1.0)
        zeta, beta = zeta + t * dz, beta + t * db
        alpha = jnp.concatenate([zeta, beta])
        u = u + t * u_d
        kkt = _kkt_padded(u, alpha, valid, params, mscale, mp)
        return alpha, u, it + 1, kkt

    def cond(carry):
        _, _, it, kkt = carry
        return jnp.logical_and(it < max_outer, kkt > tol)

    # evaluate KKT at the warm start so an already-optimal init runs zero
    # outer passes (Algorithm 1 line 5's convergence check reads this).
    # u0 is (m,) from the caller (u is linear in alpha, so a rescaled warm
    # start's cache comes for free); padded rows of Qp are zero => pad u
    # with zeros.
    if u0 is None:
        u0 = matmul(Qp, alpha[:mp] - alpha[mp:])
    else:
        u0 = jnp.pad(u0, (0, pad))
    init = (alpha, u0, jnp.int32(0), _kkt_padded(u0, alpha, valid, params,
                                                 mscale, mp))
    alpha, u, it, kkt = jax.lax.while_loop(cond, lambda c: outer(c), init)
    zeta, beta = alpha[:mp], alpha[mp:]
    out = jnp.concatenate([zeta[:m], beta[:m]])
    u = matmul(Q, zeta[:m] - beta[:m])
    return CDResult(alpha=out, u=u, sweeps=it, kkt=kkt)


def _kkt_padded(u, alpha, valid, params, mscale, mp):
    zeta, beta = alpha[:mp], alpha[mp:]
    gz = u + mscale * params.c * params.ups * zeta + (params.theta - 1.0)
    gb = -u + mscale * params.c * beta + (params.theta + 1.0)
    g = jnp.concatenate([gz, gb])
    a = jnp.concatenate([zeta, beta])
    v2 = jnp.concatenate([valid, valid])
    proj = jnp.where(a > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
    return jnp.max(jnp.where(v2, proj, 0.0))


def objective(Q: Array, alpha: Array, params: ODMParams, mscale: float) -> Array:
    return dual_objective(Q, alpha, params, mscale)
