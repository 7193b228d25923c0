"""Pallas TPU kernel: block-Gauss-Seidel dual coordinate descent tile solve.

TPU adaptation of the paper's scalar dual CD (Eqn. 3). Scalar cyclic CD is
latency-bound on TPU (one f32 op per cycle vs a 8x128 VPU), so inside each
VMEM-resident diagonal tile we run *Gauss-Southwell* (greedy) CD: every
step computes the full projected-gradient vector for the tile's 2B
coordinates (vectorized), picks the worst violator (argmax), and applies
the exact univariate update via a masked rank-1 update of the cache u.
Each step is O(B²) VPU work on 2-D (·, B) slabs (Mosaic lowers no 1-D
concatenate or 1-D matvec) — fully vectorized, no scalar HBM
round-trips. A tile *early-exits* its sweep once its in-tile
projected-KKT residual drops below the solver tolerance
(adaptive steps_per_pass), so greedy CD stops wasting steps on converged
tiles; convergence itself is still decided by the exact full-problem KKT
residual in the outer pass loop, never by the in-tile exit.

Fused pass (:func:`fused_cd_pass`): one ``pallas_call`` advances a whole
SODM level — every diagonal tile's greedy sweep AND the cross-tile Gram
matvec u_d = Q (dz - db) needed by the Jacobi line search. The grid is
(K, nblk_i, nblk_j[, n_d]): for each CD tile i (outer), the sweep runs
once (at j = 0) and its step d_i is held in VMEM scratch while the j sweep
streams Gram tiles — materialized (B, B) blocks of Q (DenseSource) or
on-the-fly tiles built from the raw features with the shared accumulation
skeleton in :mod:`repro.kernels.gram` (KernelSource) — and accumulates
K(j, i) @ d_i straight into row j of the resident (nblk, B) u_d output
block. The Gram tile never leaves VMEM and the separate per-pass XLA
matmul (or second matvec kernel launch) of the unfused path disappears:
HBM traffic per pass drops from (kernel read + matmul read) to one
streamed read.

Memory: only the (B, B) *diagonal* Gram blocks and O(m)-sized vectors
(alpha, u, u_d, labels) are resident — O(nblk·B²) = O(m·B) bytes instead
of the full O(m²) Gram on the matrix-free path. VMEM per grid step:
B² (diag tile) + B² (gram acc) + 2·B·bd (feature slabs) + ~6m/nblk·B
floats, plus the (nblk, B) u_d block (4·m bytes fp32 — 4 MB at
m = 10⁶, documented ceiling of the fused layout).

:func:`solve_level` drives the pass loop with warm-start support
(Algorithm 1 line 12) and masked padding for non-tile-multiple partitions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gram as gram_mod
from repro.precision import MATMUL

Array = jax.Array


def _greedy_tile_sweep(qblk: Array, alpha: Array, u: Array, valid: Array,
                       *, c: float, ups: float, theta: float, mscale: float,
                       n_steps: int, exit_tol: float) -> tuple[Array, Array]:
    """Greedy (Gauss-Southwell) CD on one diagonal tile, with early exit.

    qblk (B, B) diagonal Gram block; alpha (2, B) rows [zeta; beta]; u
    (1, B) cache restricted to the tile's rows (external contribution
    frozen — Jacobi); valid (1, B) marks real coordinates. Runs until
    ``n_steps`` updates have been applied or the in-tile projected-KKT
    residual (measured at the start of a step, so the exit lags one cheap
    update) drops to ``exit_tol``. ``exit_tol = 0.0`` reproduces the
    fixed-step sweep. Returns the updated (alpha (2, B), u (1, B)).

    Every value is 2-D with the coordinate index on the lanes: Mosaic
    lowers neither 1-D concatenates nor 1-D matvecs. The flat coordinate
    order (all zetas, then all betas) and every floating-point operation
    match the 1-D formulation of :func:`repro.kernels.ref.cd_tile_sweep`.
    """
    B = qblk.shape[0]
    f32 = jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    q_diag = jnp.sum(jnp.where(rows == jax.lax.broadcasted_iota(
        jnp.int32, (B, B), 1), qblk, 0.0), axis=0, keepdims=True)   # (1, B)
    # column k of qblk is row k of its transpose: the rank-1 u update
    # reads it with a sublane-masked reduce instead of a one-hot matvec
    qt = qblk.T
    rows = rows.astype(f32)
    half = jax.lax.broadcasted_iota(jnp.int32, (2, B), 0)
    flat = (half * B + jax.lax.broadcasted_iota(jnp.int32, (2, B), 1)
            ).astype(f32)                                  # flat coord index
    is_z = half == 0
    reg = jnp.where(is_z, mscale * c * ups, mscale * c).astype(alpha.dtype)
    off = jnp.where(is_z, theta - 1.0, theta + 1.0).astype(alpha.dtype)
    h = q_diag + reg

    def cond(carry):
        _, _, t, vmax = carry
        return jnp.logical_and(t < n_steps, vmax > exit_tol)

    def step(carry):
        alpha, u, t, _ = carry
        g = jnp.where(is_z, u, -u) + reg * alpha + off     # [gz; gb]
        viol = jnp.where(alpha > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
        viol = jnp.where(valid > 0.0, viol, 0.0)
        vmax = jnp.max(viol)
        i = jnp.min(jnp.where(viol == vmax, flat, 2.0 * B))  # first argmax
        sel = (flat == i).astype(alpha.dtype)               # one-hot (2, B)
        a_i = jnp.sum(alpha * sel)
        g_i = jnp.sum(g * sel)
        h_i = jnp.sum(h * sel)
        v_i = jnp.sum(valid * sel)
        new_i = jnp.maximum(a_i - g_i / h_i, 0.0)
        delta = (new_i - a_i) * v_i
        alpha = alpha + delta * sel
        k = jnp.where(i < B, i, i - B)                      # tile row of i
        sign = jnp.where(i < B, 1.0, -1.0).astype(u.dtype)  # zeta +, beta -
        col = jnp.sum(jnp.where(rows == k, qt, 0.0), axis=0, keepdims=True)
        u = u + (delta * sign) * col
        return alpha, u, t + 1, vmax

    big = jnp.asarray(jnp.finfo(alpha.dtype).max, alpha.dtype)
    alpha, u, _, _ = jax.lax.while_loop(
        cond, step, (alpha, u, jnp.int32(0), big))
    return alpha, u


def _cd_tile_kernel(q_ref, alpha_ref, u_ref, valid_ref, alpha_out, u_out, *,
                    c: float, ups: float, theta: float, mscale: float,
                    n_steps: int, exit_tol: float):
    """One (B, B) diagonal tile of the standalone sweep kernel.

    Padded coordinates (valid = 0) are frozen at zero: their violation is
    masked so greedy never selects them and they never perturb u.
    """
    alpha_out[...], u_out[...] = _greedy_tile_sweep(
        q_ref[...], alpha_ref[...], u_ref[...], valid_ref[...], c=c, ups=ups,
        theta=theta, mscale=mscale, n_steps=n_steps, exit_tol=exit_tol)


@functools.partial(jax.jit, static_argnames=("c", "ups", "theta", "mscale",
                                             "n_steps", "exit_tol",
                                             "interpret"))
def cd_block_sweep(q_blocks: Array, alphas: Array, us: Array, *, c: float,
                   ups: float, theta: float, mscale: float, n_steps: int,
                   valids: Array | None = None, exit_tol: float = 0.0,
                   interpret: bool = False) -> tuple[Array, Array]:
    """Run up to n_steps greedy-CD updates inside every diagonal tile.

    q_blocks (nblk, B, B), alphas (nblk, 2B), us (nblk, B) ->
    (alphas', us'). ``valids`` (nblk, B) marks real coordinates (1.0) vs
    padding (0.0); padded coordinates are frozen at zero. Defaults to all
    valid. ``exit_tol > 0`` lets a tile stop its sweep once its in-tile
    KKT residual drops below it (adaptive steps_per_pass).
    """
    nblk, B, _ = q_blocks.shape
    if valids is None:
        valids = jnp.ones((nblk, B), q_blocks.dtype)
    kernel = functools.partial(_cd_tile_kernel, c=c, ups=ups, theta=theta,
                               mscale=mscale, n_steps=n_steps,
                               exit_tol=exit_tol)
    # per-tile vectors ride as (2, B) / (1, B) slabs: the last two block
    # dims then equal the array's, as the TPU lowering requires
    a_spec = pl.BlockSpec((None, 2, B), lambda b: (b, 0, 0))
    v_spec = pl.BlockSpec((None, 1, B), lambda b: (b, 0, 0))
    a, u = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((None, B, B), lambda b: (b, 0, 0)),
                  a_spec, v_spec, v_spec],
        out_specs=[a_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct((nblk, 2, B), alphas.dtype),
                   jax.ShapeDtypeStruct((nblk, 1, B), us.dtype)],
        interpret=interpret,
    )(q_blocks, alphas.reshape(nblk, 2, B), us.reshape(nblk, 1, B),
      valids.reshape(nblk, 1, B))
    return a.reshape(nblk, 2 * B), u.reshape(nblk, B)


def extract_diag_blocks(Q: Array, block: int) -> Array:
    """(M, M) -> (M/block, block, block) diagonal blocks."""
    M = Q.shape[0]
    nblk = M // block
    idx = jnp.arange(nblk)
    return jax.vmap(lambda b: jax.lax.dynamic_slice(
        Q, (b * block, b * block), (block, block)))(idx)


# ---------------------------------------------------------------------------
# fused pass: tile sweeps + accumulating Gram matvec, one pallas_call
# ---------------------------------------------------------------------------

def _sweep_step(qb_ref, a_ref, u_ref, v_ref, a_out, cd: dict) -> Array:
    """Run tile i's greedy sweep, write its alphas, return its Jacobi step
    d_i = dz_i - db_i as a (1, B) row."""
    a_old = a_ref[...]
    a_new, _ = _greedy_tile_sweep(qb_ref[...], a_old, u_ref[...],
                                  v_ref[...], **cd)
    a_out[...] = a_new
    step = a_new - a_old
    return step[0:1, :] - step[1:2, :]


def _fused_dense_kernel(qb_ref, a_ref, u_ref, v_ref, q_ref, a_out, ud_out,
                        d_ref, *, cd: dict):
    """Fused pass over a materialized signed Q. Grid (K, nblk_i, nblk_j).

    At j = 0 the CD sweep for tile i runs and its Jacobi step
    d_i = dz_i - db_i is parked in a (B, 1) scratch; every j then streams
    the (B, B) block Q[jB:, iB:] and accumulates Q(j, i) @ d_i into row j
    of the partition-resident (nblk, B) u_d output block.
    """
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _zero_ud():
        ud_out[...] = jnp.zeros_like(ud_out)

    @pl.when(j == 0)
    def _sweep():
        d_ref[...] = _sweep_step(qb_ref, a_ref, u_ref, v_ref, a_out,
                                 cd).astype(jnp.float32).T

    contrib = jax.lax.dot_general(                 # Q(j, i) @ d_i: (B, 1)
        q_ref[0].astype(jnp.float32), d_ref[...],
        (((1,), (0,)), ((), ())), precision=MATMUL,
        preferred_element_type=jnp.float32)
    ud_out[j, :] = ud_out[j, :] + contrib[:, 0].astype(ud_out.dtype)


def _fused_mf_kernel(qb_ref, a_ref, u_ref, v_ref, yi_ref, yj_ref, xxr_ref,
                     xxc_ref, xr_ref, xc_ref, a_out, ud_out, acc_ref, d_ref,
                     *, kind: str, gamma: float, degree: int, coef0: float,
                     n_d: int, cd: dict):
    """Matrix-free fused pass. Grid (K, nblk_i, nblk_j, n_d).

    Identical control flow to the dense variant, but the Gram tile
    K(j, i) is rebuilt in the acc scratch from feature slabs with the
    shared skeleton (:mod:`repro.kernels.gram`) across the innermost D
    sweep. Labels fold in as Q = y yᵀ ⊙ K: the parked step is
    d_i ⊙ y_i and each row contribution is scaled by y_j, so padded rows
    (label 0) vanish without masking any tile.
    """
    i = pl.program_id(1)
    j = pl.program_id(2)
    kd = pl.program_id(3)

    @pl.when(jnp.logical_and(i == 0, jnp.logical_and(j == 0, kd == 0)))
    def _zero_ud():
        ud_out[...] = jnp.zeros_like(ud_out)

    @pl.when(jnp.logical_and(j == 0, kd == 0))
    def _sweep():
        d = _sweep_step(qb_ref, a_ref, u_ref, v_ref, a_out, cd)
        d_ref[...] = (yi_ref[...] * d).astype(jnp.float32).T

    @pl.when(kd == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = gram_mod.accum_tile(kind, acc_ref[...], xr_ref[...],
                                       xc_ref[...])

    @pl.when(kd == n_d - 1)
    def _contract():
        k = gram_mod.finalize_tile(kind, acc_ref[...], xxr_ref[0, :],
                                   xxc_ref[0, :], gamma=gamma,
                                   degree=degree, coef0=coef0)
        contrib = jax.lax.dot_general(             # K(j, i) @ (y_i ⊙ d_i)
            k, d_ref[...], (((1,), (0,)), ((), ())),
            precision=MATMUL, preferred_element_type=jnp.float32)[:, 0]
        ud_out[j, :] = ud_out[j, :] + (yj_ref[0, :] * contrib).astype(
            ud_out.dtype)


def fused_cd_pass(q_blocks: Array, src, alphas: Array, us: Array,
                  valids: Array, *, c: float, ups: float, theta: float,
                  mscale: float, n_steps: int, exit_tol: float,
                  interpret: bool = False) -> tuple[Array, Array]:
    """One fused Jacobi pass for a whole level: ONE ``pallas_call``.

    q_blocks (K, nblk, B, B) diagonal blocks; ``src`` a
    :class:`~repro.kernels.gram.DenseSource` or
    :class:`~repro.kernels.gram.KernelSource` supplying the off-diagonal
    mass; alphas (K, nblk, 2B) per-tile [zeta; beta]; us (K, nblk, B);
    valids (K, nblk, B). Returns (alphas' (K, nblk, 2B),
    u_d (K, m) = Q (dz - db)) — everything the caller's exact line search
    needs, with no separate matvec.

    Inside the call every per-tile vector is a (2, B) or (1, B) slab and
    u_d a resident (nblk, B) block, so the last two dims of every block
    equal the array's (the TPU lowering's rule for non-(8, 128) blocks).
    """
    K, nblk, B, _ = q_blocks.shape
    m = nblk * B
    cd = dict(c=c, ups=ups, theta=theta, mscale=mscale, n_steps=n_steps,
              exit_tol=exit_tol)
    out_shape = [
        jax.ShapeDtypeStruct((K, nblk, 2, B), alphas.dtype),
        jax.ShapeDtypeStruct((K, nblk, B), us.dtype),
    ]
    a_spec = pl.BlockSpec((None, None, 2, B), lambda k, i, j, *d: (k, i, 0, 0))
    v_spec = pl.BlockSpec((None, None, 1, B), lambda k, i, j, *d: (k, i, 0, 0))
    cd_specs = [
        pl.BlockSpec((None, None, B, B), lambda k, i, j, *d: (k, i, 0, 0)),
        a_spec, v_spec, v_spec,                                  # a, u, valid
    ]
    out_specs = [
        a_spec,                                                  # a'
        pl.BlockSpec((None, nblk, B), lambda k, i, j, *d: (k, 0, 0)),  # u_d
    ]
    cd_args = (q_blocks, alphas.reshape(K, nblk, 2, B),
               us.reshape(K, nblk, 1, B), valids.reshape(K, nblk, 1, B))
    if isinstance(src, gram_mod.DenseSource):
        kernel = functools.partial(_fused_dense_kernel, cd=cd)
        a, ud = pl.pallas_call(
            kernel,
            grid=(K, nblk, nblk),
            in_specs=cd_specs + [
                pl.BlockSpec((1, B, B), lambda k, i, j: (k, j, i)),     # Q
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[gram_mod._scratch((B, 1))],
            interpret=interpret,
        )(*cd_args, src.q)
        return a.reshape(K, nblk, 2 * B), ud.reshape(K, m)

    bd = src.bd
    n_d = src.x.shape[-1] // bd
    xx = gram_mod.row_norms(src.x)[:, None, :].astype(src.x.dtype)  # (K,1,m)
    kernel = functools.partial(_fused_mf_kernel, kind=src.kind,
                               gamma=src.gamma, degree=src.degree,
                               coef0=src.coef0, n_d=n_d, cd=cd)
    at_i = pl.BlockSpec((None, 1, B), lambda k, i, j, d: (k, 0, i))
    at_j = pl.BlockSpec((None, 1, B), lambda k, i, j, d: (k, 0, j))
    a, ud = pl.pallas_call(
        kernel,
        grid=(K, nblk, nblk, n_d),
        in_specs=cd_specs + [
            at_i, at_j,                                          # y_i, y_j
            at_j, at_i,                                          # xx_j, xx_i
            pl.BlockSpec((None, B, bd), lambda k, i, j, d: (k, j, d)),  # x_j
            pl.BlockSpec((None, B, bd), lambda k, i, j, d: (k, i, d)),  # x_i
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[gram_mod._scratch((B, B)), gram_mod._scratch((B, 1))],
        interpret=interpret,
    )(*cd_args, src.y[:, None, :], src.y[:, None, :], xx, xx, src.x, src.x)
    return a.reshape(K, nblk, 2 * B), ud.reshape(K, m)


# ---------------------------------------------------------------------------
# level solve: fused pass loop + exact line search + exact KKT stop
# ---------------------------------------------------------------------------

def solve_level(q_blocks: Array, src, alphas0: Array, *, c: float,
                ups: float, theta: float, mscale: float,
                steps_per_pass: int | None = None, n_passes: int = 30,
                tol: float = 1e-5, valid: Array | None = None,
                us0: Array | None = None, adaptive: bool = True,
                fused: bool | None = None,
                interpret: bool = False) -> tuple[Array, Array, Array]:
    """Block-CD solve of K same-size partitions, one ``pallas_call`` per pass.

    This is SODM's per-level engine: all K local ODM duals of one level are
    advanced together by :func:`fused_cd_pass` — tile sweeps AND the
    cross-tile Gram matvec in a single kernel launch per pass, for any
    supported gram source.

    Args:
      q_blocks: (K, nblk, B, B) diagonal Gram blocks of each partition.
      src:      gram source for the off-diagonal mass —
                :class:`~repro.kernels.gram.DenseSource` (materialized Q)
                or :class:`~repro.kernels.gram.KernelSource` (on-the-fly
                tiles, O(m·B) memory).
      alphas0:  (K, 2m) warm starts — Algorithm 1 line 12 passes the merged
                child solutions here; zeros give a cold start.
      valid:    (m,) mask of real vs padded coordinates, shared by all
                partitions (they are equal-sized). Padded coordinates stay
                frozen at zero and are excluded from the KKT residual, so
                padding never delays convergence or fakes violations.
      us0:      optional (K, m) precomputed matvec(zeta0 - beta0) — u is
                linear in alpha, so callers that already paid the matvec
                (e.g. for a warm-start rescale) pass the scaled cache here
                and skip the init matvec.
      adaptive: early-exit each tile's greedy sweep once its in-tile KKT
                residual drops below 0.01·tol (never changes the
                convergence criterion — the outer stop is always the
                exact full-problem KKT residual).
      fused:    run each pass as ONE :func:`fused_cd_pass` launch (sweeps
                + in-kernel Gram matvec). Default (None) picks fused when
                compiled and the mathematically identical two-launch
                layout (sweep kernel + ``src.matvec``) under interpret
                mode: the interpreter unrolls the grid into the trace, so
                the fused nblk² grid would bloat CPU compile time
                quadratically while the win it buys (one kernel launch,
                halved HBM round-trips) only exists on real hardware.

    The outer while_loop is shared across partitions (Jacobi): it stops when
    the *worst* partition's projected-KKT residual drops below tol. Each
    pass is safeguarded by an exact line search along the joint Jacobi step
    (f(alpha + t·d) is quadratic in t and u moves linearly, so the optimal
    damping is closed-form and reuses the fused pass's matvec): t = 1 when
    tiles don't conflict; t < 1 tames off-diagonal mass that would
    otherwise make simultaneous tile updates diverge (weakly regularized /
    Q-dominant duals). The KKT of the warm start is evaluated before the
    first pass so an already-optimal init returns 0 passes (Algorithm 1
    line 5's early-stop convergence check reads this).

    Returns (alphas (K, 2m), kkts (K,), passes ()).
    """
    K, nblk, B, _ = q_blocks.shape
    m = nblk * B
    n_steps = 2 * B if steps_per_pass is None else steps_per_pass
    if fused is None:
        fused = not interpret
    # the in-tile exit is two decades tighter than the outer stop so an
    # exited tile is converged *relative to* the full-problem check — the
    # adaptive path then never pays extra outer passes for the steps the
    # fixed sweep would have spent polishing an already-converged tile
    exit_tol = 0.01 * tol if adaptive else 0.0
    if valid is None:
        valid = jnp.ones((m,), q_blocks.dtype)
    valid = valid.astype(q_blocks.dtype)
    valids = jnp.broadcast_to(valid.reshape(1, nblk, B), (K, nblk, B))
    valid2 = jnp.concatenate([valid, valid])[None, :]      # (1, 2m)

    def kkt(alphas, us):
        zetas, betas = alphas[:, :m], alphas[:, m:]
        gz = us + mscale * c * ups * zetas + (theta - 1.0)
        gb = -us + mscale * c * betas + (theta + 1.0)
        g = jnp.concatenate([gz, gb], axis=1)
        viol = jnp.where(alphas > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
        return jnp.max(jnp.where(valid2 > 0.0, viol, 0.0), axis=1)   # (K,)

    def body(carry):
        alphas, us, _, it = carry
        zetas, betas = alphas[:, :m], alphas[:, m:]
        a_t = jnp.concatenate([zetas.reshape(K, nblk, B),
                               betas.reshape(K, nblk, B)], axis=2)
        if fused:
            a_t, u_d = fused_cd_pass(q_blocks, src, a_t,
                                     us.reshape(K, nblk, B), valids, c=c,
                                     ups=ups, theta=theta, mscale=mscale,
                                     n_steps=n_steps, exit_tol=exit_tol,
                                     interpret=interpret)
            z_new = a_t[:, :, :B].reshape(K, m)
            b_new = a_t[:, :, B:].reshape(K, m)
            dz, db = z_new - zetas, b_new - betas
        else:
            # two-launch layout: same sweep helper, same math — the Gram
            # matvec just rides a second launch (src.matvec) instead of
            # accumulating inside the sweep kernel
            a2, _ = cd_block_sweep(
                q_blocks.reshape(K * nblk, B, B),
                a_t.reshape(K * nblk, 2 * B),
                us.reshape(K * nblk, B), c=c, ups=ups, theta=theta,
                mscale=mscale, n_steps=n_steps, exit_tol=exit_tol,
                valids=valids.reshape(K * nblk, B), interpret=interpret)
            a2 = a2.reshape(K, nblk, 2 * B)
            z_new = a2[:, :, :B].reshape(K, m)
            b_new = a2[:, :, B:].reshape(K, m)
            dz, db = z_new - zetas, b_new - betas
            u_d = src.matvec(dz - db)
        # exact line search along each partition's joint Jacobi step; the
        # matvec u_d = Q (dz - db) it needs came out of the fused pass
        gz = us + mscale * c * ups * zetas + (theta - 1.0)
        gb = -us + mscale * c * betas + (theta + 1.0)
        gdot = jnp.sum(gz * dz + gb * db, axis=1)
        quad = jnp.sum((dz - db) * u_d, axis=1) + mscale * c * jnp.sum(
            ups * dz * dz + db * db, axis=1)
        t = jnp.where(quad > 0.0,
                      jnp.clip(-gdot / jnp.maximum(quad, 1e-30), 0.0, 1.0),
                      1.0)[:, None]
        zetas, betas = zetas + t * dz, betas + t * db
        alphas = jnp.concatenate([zetas, betas], axis=1)
        us = us + t * u_d
        return alphas, us, kkt(alphas, us), it + 1

    def cond(carry):
        _, _, r, it = carry
        return jnp.logical_and(it < n_passes, jnp.max(r) > tol)

    if us0 is None:
        zetas0, betas0 = alphas0[:, :m], alphas0[:, m:]
        us0 = src.matvec(zetas0 - betas0)
    init = (alphas0, us0, kkt(alphas0, us0), jnp.int32(0))
    alphas, _, r, it = jax.lax.while_loop(cond, body, init)
    return alphas, r, it


def solve(Q: Array, *, c: float, ups: float, theta: float, mscale: float,
          block: int = 256, steps_per_pass: int | None = None,
          n_passes: int = 30, tol: float = 1e-5, alpha0: Array | None = None,
          valid: Array | None = None, adaptive: bool = True,
          fused: bool | None = None,
          interpret: bool = False) -> tuple[Array, Array, Array]:
    """Full block-CD solve driven by the fused Pallas pass kernel.

    Outer loop (lax.while_loop): one fused pass (tile sweeps + Gram
    matvec), exact line search, global projected-KKT check. ``alpha0`` is
    the warm start (defaults to zeros); a warm start already within tol
    returns 0 passes. ``valid`` marks real vs padded coordinates (see
    :func:`solve_level`). Returns (alpha, kkt, passes).
    """
    M = Q.shape[0]
    assert M % block == 0, (M, block)
    qb = extract_diag_blocks(Q, block)[None]               # (1, nblk, B, B)
    a0 = jnp.zeros(2 * M, Q.dtype) if alpha0 is None else alpha0
    alphas, r, it = solve_level(
        qb, gram_mod.DenseSource(Q[None]), a0[None], c=c, ups=ups,
        theta=theta, mscale=mscale, steps_per_pass=steps_per_pass,
        n_passes=n_passes, tol=tol, valid=valid, adaptive=adaptive,
        fused=fused, interpret=interpret)
    return alphas[0], r[0], it
