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

A greedy step is a serial chain (max, first argmax, one-hot sums, update,
column read) whose latency, not its arithmetic, sets the sweep's time,
and one tile's [zeta; beta] state fills 2 of a vreg's 8 sublanes. The
tiles of a pass are independent (Jacobi), so :func:`group_size` of them
(4 in float32) sweep in lockstep: their states stack into (G, B) slabs,
every reduction runs per row, and one step's chain serves the group. A
tile that has exited keeps its state, so each tile ends exactly where it
would alone; a group costs its slowest tile, and the per-tile step counts
(:func:`step_counts`) say how full the groups ran.

Fused pass (:func:`fused_cd_pass`): one ``pallas_call`` advances a whole
SODM level — every diagonal tile's greedy sweep AND the cross-tile Gram
matvec u_d = Q (dz - db) needed by the Jacobi line search. The grid is
(K, ngrp, G, nblk_j[, n_d]): for each group of G column tiles, the
lockstep sweep runs once (at its first step) and the tiles' steps d_i
are held in VMEM scratch while (g, j) streams Gram tiles — materialized
(B, B) blocks of Q (DenseSource) or on-the-fly tiles built from the raw
features with the shared accumulation skeleton in
:mod:`repro.kernels.gram` (KernelSource) — and accumulates K(j, i) @ d_i
straight into row j of the resident (nblk, B) u_d output block, tile by
tile in the order of a grid over single tiles. The Gram tile never
leaves VMEM and the separate per-pass XLA matmul (or second matvec
kernel launch) of the unfused path disappears: HBM traffic per pass
drops from (kernel read + matmul read) to one streamed read.

Memory: only the (B, B) *diagonal* Gram blocks and O(m)-sized vectors
(alpha, u, u_d, labels) are resident — O(nblk·B²) = O(m·B) bytes instead
of the full O(m²) Gram on the matrix-free path. VMEM per grid step:
2·G·B² (the group's transposed diagonal tiles, double-buffered) + B²
(gram acc) + 2·B·bd (feature slabs) + ~7·G·B floats, plus the (nblk, B)
u_d block (4·m bytes fp32 — 4 MB at m = 10⁶, documented ceiling of the
fused layout).

:func:`solve_level` drives the pass loop with warm-start support
(Algorithm 1 line 12) and masked padding for non-tile-multiple partitions.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gram as gram_mod
from repro.precision import MATMUL

Array = jax.Array


def group_size(dtype) -> int:
    """Diagonal tiles swept in lockstep: the sublanes of a vreg in
    ``dtype`` (8 for float32) over the two rows, [zeta; beta], of one
    tile's state."""
    return 32 // jnp.dtype(dtype).itemsize // 2


def step_counts(steps: Array, group: int) -> Array:
    """Per-tile applied greedy steps (K, nblk) -> (K, 2) int32 per
    partition: the steps its tiles applied (``tile_steps``), and the step
    slots their lockstep groups held (``step_slots``: each group of
    ``group`` consecutive tiles runs as long as its longest sweep)."""
    K, nblk = steps.shape
    pad = -nblk % group
    longest = jnp.pad(steps, ((0, 0), (0, pad))).reshape(K, -1, group)
    return jnp.stack([jnp.sum(steps, axis=1),
                      group * jnp.sum(jnp.max(longest, axis=2), axis=1)],
                     axis=1).astype(jnp.int32)


def _greedy_group_sweep(qt_refs, q_diag: Array, z: Array, b: Array,
                        u: Array, valid: Array, live: Array, *, c: float,
                        ups: float, theta: float, mscale: float,
                        n_steps: int, exit_tol: float):
    """Greedy (Gauss-Southwell) CD on G diagonal tiles in lockstep, each
    with its own early exit.

    Row g of every (G, B) slab is tile g: ``z``/``b`` its duals
    [zeta; beta], ``u`` its cache (external contribution frozen —
    Jacobi), ``valid`` its real coordinates, ``q_diag`` the diagonal of
    its block; ``qt_refs[g]`` holds tile g's transposed (B, B) block. ``live``
    (G, 1) is 0 for a tile that takes no step at all. A tile runs until
    ``n_steps`` updates have been applied or its in-tile projected-KKT
    residual (measured at the start of a step, so the exit lags one cheap
    update) drops to ``exit_tol``; ``exit_tol = 0.0`` reproduces the
    fixed-step sweep. The loop runs while any tile is live, and a tile
    that has exited keeps its state, so each tile's result and step count
    are exactly those of a sweep of that tile alone; a group costs its
    slowest tile. Returns (z, b, u, steps (G, 1) int32).

    Every per-tile quantity is a lane reduction over the tile's own rows:
    max, first argmax and one-hot sums each have one candidate term, so
    they are exact. The flat coordinate order (all zetas, then all betas)
    and every floating-point operation match the 1-D formulation of
    :func:`repro.kernels.ref.cd_tile_sweep`.
    """
    G, B = z.shape
    f32 = jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0).astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, B), 1).astype(f32)
    reg_z, reg_b = mscale * c * ups, mscale * c
    off_z, off_b = theta - 1.0, theta + 1.0
    h_z, h_b = q_diag + reg_z, q_diag + reg_b
    first = 2.0 * B                       # past every flat coordinate

    def cond(carry):
        *_, t, live, _ = carry
        return jnp.logical_and(t < n_steps, jnp.max(live) > 0.0)

    def step(carry):
        z, b, u, t, live, steps = carry
        g_z = u + reg_z * z + off_z
        g_b = -u + reg_b * b + off_b
        v_z = jnp.where(z > 0.0, jnp.abs(g_z), jnp.maximum(-g_z, 0.0))
        v_b = jnp.where(b > 0.0, jnp.abs(g_b), jnp.maximum(-g_b, 0.0))
        v_z = jnp.where(valid > 0.0, v_z, 0.0)
        v_b = jnp.where(valid > 0.0, v_b, 0.0)
        vmax = jnp.maximum(jnp.max(v_z, axis=1, keepdims=True),
                           jnp.max(v_b, axis=1, keepdims=True))   # (G, 1)
        i = jnp.minimum(                                  # first argmax
            jnp.min(jnp.where(v_z == vmax, lane, first), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(v_b == vmax, lane + B, first), axis=1,
                    keepdims=True))
        sel_z = (lane == i).astype(z.dtype)               # one-hot rows
        sel_b = (lane + B == i).astype(z.dtype)

        def pick(x_z, x_b):
            return jnp.sum(x_z * sel_z + x_b * sel_b, axis=1, keepdims=True)

        a_i = pick(z, b)
        new_i = jnp.maximum(a_i - pick(g_z, g_b) / pick(h_z, h_b), 0.0)
        delta = (new_i - a_i) * pick(valid, valid)
        k = jnp.where(i < B, i, i - B)                    # tile row of i
        sign = jnp.where(i < B, 1.0, -1.0).astype(u.dtype)  # zeta +, beta -
        # column k of each block is row k of its transpose: a masked
        # sublane reduce per tile instead of a one-hot matvec
        col = jnp.concatenate([
            jnp.sum(jnp.where(rows == k[g:g + 1], qt_refs[g][...], 0.0),
                    axis=0, keepdims=True) for g in range(G)])
        on = live > 0.0
        z = jnp.where(on, z + delta * sel_z, z)
        b = jnp.where(on, b + delta * sel_b, b)
        u = jnp.where(on, u + (delta * sign) * col, u)
        return (z, b, u, t + 1, jnp.where(vmax > exit_tol, live, 0.0),
                steps + live)

    z, b, u, _, _, steps = jax.lax.while_loop(
        cond, step, (z, b, u, jnp.int32(0), live, jnp.zeros_like(live)))
    return z, b, u, steps.astype(jnp.int32)


def _sweep_group(qt_refs, slab_refs, first, nblk, cd: dict):
    """Sweep one group: tile ``first + g`` sits in row g of every slab
    (``slab_refs``: z, b, u, valid, q_diag) and in ``qt_refs[g]``. Rows
    at or past ``nblk`` pad the last group and never step."""
    z_ref, b_ref, u_ref, v_ref, qd_ref = slab_refs
    G = z_ref.shape[0]
    live = (jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0) + first
            < nblk).astype(z_ref.dtype)
    return _greedy_group_sweep(qt_refs, qd_ref[...], z_ref[...], b_ref[...],
                               u_ref[...], v_ref[...], live, **cd)


def _grouped(v: Array, group: int) -> Array:
    """(K, nblk, B) per-tile rows -> (K, ngrp, group, B), the last group
    padded with zero tiles."""
    K, nblk, B = v.shape
    v = jnp.pad(v, ((0, 0), (0, -nblk % group), (0, 0)))
    return v.reshape(K, -1, group, B)


def _sweep_operands(q_blocks: Array, alphas: Array, us: Array,
                    valids: Array, group: int):
    """What a lockstep sweep reads: the transposed diagonal blocks
    (column k of a block is row k of its transpose), and the per-tile
    (K, ngrp, group, B) slabs of zeta, beta, u, valid and the blocks'
    diagonals."""
    K, nblk, B, _ = q_blocks.shape
    a = alphas.reshape(K, nblk, 2, B)
    rows = (a[:, :, 0], a[:, :, 1], us.reshape(K, nblk, B),
            valids.reshape(K, nblk, B),
            jnp.diagonal(q_blocks, axis1=2, axis2=3))
    return (jnp.swapaxes(q_blocks, 2, 3),
            [_grouped(v, group) for v in rows])


def _cd_group_kernel(*refs, group: int, nblk: int, cd: dict):
    """One group of the standalone sweep kernel. Grid (K, ngrp).

    Padded coordinates (valid = 0) are frozen at zero: their violation is
    masked so greedy never selects them and they never perturb u.
    """
    z_out, b_out, u_out, s_out = refs[-4:]
    z_out[...], b_out[...], u_out[...], s_out[...] = _sweep_group(
        refs[:group], refs[group:-4], pl.program_id(1) * group, nblk, cd)


@functools.partial(jax.jit, static_argnames=("c", "ups", "theta", "mscale",
                                             "n_steps", "exit_tol",
                                             "interpret"))
def cd_block_sweep(q_blocks: Array, alphas: Array, us: Array, *, c: float,
                   ups: float, theta: float, mscale: float, n_steps: int,
                   valids: Array | None = None, exit_tol: float = 0.0,
                   interpret: bool = False) -> tuple[Array, Array, Array]:
    """Run up to n_steps greedy-CD updates inside every diagonal tile.

    q_blocks (..., nblk, B, B), alphas (..., nblk, 2B), us (..., nblk, B)
    -> (alphas', us', steps (..., nblk) int32, each tile's applied
    updates). Tiles are swept :func:`group_size` at a time in lockstep
    within each leading index. ``valids`` (..., nblk, B) marks real
    coordinates (1.0) vs padding (0.0); padded coordinates are frozen at
    zero. Defaults to all valid. ``exit_tol > 0`` lets a tile stop its
    sweep once its in-tile KKT residual drops below it (adaptive
    steps_per_pass).
    """
    *lead, nblk, B, _ = q_blocks.shape
    K = math.prod(lead)
    G = group_size(alphas.dtype)
    if valids is None:
        valids = jnp.ones(q_blocks.shape[:-1], q_blocks.dtype)
    qt_blocks, slabs = _sweep_operands(q_blocks.reshape(K, nblk, B, B),
                                       alphas, us, valids, G)
    ngrp = slabs[0].shape[1]

    def diag(g):                      # the last group's missing tiles clamp
        return pl.BlockSpec((None, None, B, B), lambda k, i: (
            k, jnp.minimum(i * G + g, nblk - 1), 0, 0))

    # per-group (G, B) slabs: the last two block dims equal the array's,
    # as the TPU lowering requires
    g_spec = pl.BlockSpec((None, None, G, B), lambda k, i: (k, i, 0, 0))
    s_spec = pl.BlockSpec((None, None, G, 1), lambda k, i: (k, i, 0, 0))
    z, b, u, s = pl.pallas_call(
        functools.partial(_cd_group_kernel, group=G, nblk=nblk, cd=dict(
            c=c, ups=ups, theta=theta, mscale=mscale, n_steps=n_steps,
            exit_tol=exit_tol)),
        grid=(K, ngrp),
        in_specs=[diag(g) for g in range(G)] + [g_spec] * len(slabs),
        out_specs=[g_spec] * 3 + [s_spec],
        out_shape=[jax.ShapeDtypeStruct(slabs[0].shape, alphas.dtype)] * 2
        + [jax.ShapeDtypeStruct(slabs[0].shape, us.dtype),
           jax.ShapeDtypeStruct((K, ngrp, G, 1), jnp.int32)],
        interpret=interpret,
    )(*[qt_blocks] * G, *slabs)

    def tiles(v):
        return v.reshape(K, ngrp * G, -1)[:, :nblk]

    alphas = jnp.concatenate([tiles(z), tiles(b)], axis=-1)
    return (alphas.reshape(*lead, nblk, 2 * B),
            tiles(u).reshape(*lead, nblk, B), tiles(s).reshape(*lead, nblk))


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

def _park_sweep(qt_refs, slab_refs, a_out, s_out, d_ref, y, first,
                nblk: int, cd: dict) -> None:
    """Sweep the group of tiles ``first`` on, write their duals and step
    counts, and park each tile's Jacobi step d = dz - db (times its labels
    ``y``, if given) in ``d_ref[g]`` as a (B, 1) column."""
    G = len(qt_refs)
    z0, b0 = slab_refs[0][...], slab_refs[1][...]
    z, b, _, s_out[...] = _sweep_group(qt_refs, slab_refs, first, nblk, cd)
    d = (z - z0) - (b - b0)
    if y is not None:
        d = y * d
    a_out[:, 0, :] = z
    a_out[:, 1, :] = b
    for g in range(G):
        d_ref[g] = d[g:g + 1].astype(jnp.float32).T


def _fused_dense_kernel(*refs, nblk: int, cd: dict):
    """Fused pass over a materialized signed Q. Grid (K, ngrp, G, nblk).

    At the first step of group i its G tiles' CD sweeps run in lockstep
    and each tile's Jacobi step is parked in the (G, B, 1) scratch; every
    (g, j) then streams the (B, B) block Q[jB:, (iG + g)B:] and
    accumulates Q(j, iG + g) @ d_{iG+g} into row j of the
    partition-resident (nblk, B) u_d output block, in the tile order of
    a grid over single tiles. Padding tiles of the last group never step,
    so their products add exact zeros (masking them out cost every Gram
    step a branch: ≈ 12 % of the Gram time on a v5e).
    """
    d_ref = refs[-1]
    G = d_ref.shape[0]
    slab_refs = refs[G:G + 5]
    q_ref, a_out, ud_out, s_out = refs[G + 5:-1]
    i, g, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, g == 0), j == 0))
    def _zero_ud():
        ud_out[...] = jnp.zeros_like(ud_out)

    @pl.when(jnp.logical_and(g == 0, j == 0))
    def _sweep():
        _park_sweep(refs[:G], slab_refs, a_out, s_out, d_ref, None, i * G,
                    nblk, cd)

    contrib = jax.lax.dot_general(                 # Q(j, i) @ d_i: (B, 1)
        q_ref[0].astype(jnp.float32), d_ref[g],
        (((1,), (0,)), ((), ())), precision=MATMUL,
        preferred_element_type=jnp.float32)
    ud_out[j, :] = ud_out[j, :] + contrib[:, 0].astype(ud_out.dtype)


def _fused_mf_kernel(*refs, kind: str, gamma: float, degree: int,
                     coef0: float, n_d: int, nblk: int, cd: dict):
    """Matrix-free fused pass. Grid (K, ngrp, G, nblk, n_d).

    Identical control flow to the dense variant, but the Gram tile
    K(j, i) is rebuilt in the acc scratch from feature slabs with the
    shared skeleton (:mod:`repro.kernels.gram`) across the innermost D
    sweep. Labels fold in as Q = y yᵀ ⊙ K: the parked step is
    d_i ⊙ y_i and each row contribution is scaled by y_j, so padded rows
    (label 0) vanish without masking any tile.
    """
    d_ref = refs[-1]
    G = d_ref.shape[0]
    slab_refs = refs[G:G + 5]
    (yi_ref, yj_ref, xxr_ref, xxc_ref, xr_ref, xc_ref, a_out, ud_out, s_out,
     acc_ref) = refs[G + 5:-1]
    i, g, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    kd = pl.program_id(4)

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, g == 0),
                             jnp.logical_and(j == 0, kd == 0)))
    def _zero_ud():
        ud_out[...] = jnp.zeros_like(ud_out)

    @pl.when(jnp.logical_and(g == 0, jnp.logical_and(j == 0, kd == 0)))
    def _sweep():
        _park_sweep(refs[:G], slab_refs, a_out, s_out, d_ref, yi_ref[...],
                    i * G, nblk, cd)

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
            k, d_ref[g], (((1,), (0,)), ((), ())),
            precision=MATMUL, preferred_element_type=jnp.float32)[:, 0]
        ud_out[j, :] = ud_out[j, :] + (yj_ref[0, :] * contrib).astype(
            ud_out.dtype)


def fused_cd_pass(q_blocks: Array, src, alphas: Array, us: Array,
                  valids: Array, *, c: float, ups: float, theta: float,
                  mscale: float, n_steps: int, exit_tol: float,
                  interpret: bool = False) -> tuple[Array, Array, Array]:
    """One fused Jacobi pass for a whole level: ONE ``pallas_call``.

    q_blocks (K, nblk, B, B) diagonal blocks; ``src`` a
    :class:`~repro.kernels.gram.DenseSource` or
    :class:`~repro.kernels.gram.KernelSource` supplying the off-diagonal
    mass; alphas (K, nblk, 2B) per-tile [zeta; beta]; us (K, nblk, B);
    valids (K, nblk, B). Returns (alphas' (K, nblk, 2B),
    u_d (K, m) = Q (dz - db), steps (K, nblk) int32, each tile's applied
    greedy updates) — everything the caller's exact line search needs,
    with no separate matvec.

    The grid is (K, ngrp, G, nblk[, n_d]): group i's G = :func:`group_size`
    diagonal tiles sweep in lockstep at its first step, then g steps over
    them as a grid over single tiles would, so every Gram tile and every
    addition into u_d is as in a one-tile sweep. A ragged last group is
    padded with tiles that never step, so their Gram products add zeros;
    the blocks of its missing tiles read the last real tile (clamped), so
    nothing of size O(m·B) is padded. Inside the call the group's
    per-tile vectors are (G, B) slabs, the duals' output a (G, 2, B)
    block and u_d a resident (nblk, B) block, so the last two dims of
    every block equal the array's (the TPU lowering's rule for
    non-(8, 128) blocks).
    """
    K, nblk, B, _ = q_blocks.shape
    m = nblk * B
    G = group_size(alphas.dtype)
    ngrp = -(-nblk // G)
    cd = dict(c=c, ups=ups, theta=theta, mscale=mscale, n_steps=n_steps,
              exit_tol=exit_tol)
    qt_blocks, slabs = _sweep_operands(q_blocks, alphas, us, valids, G)

    def col(i, g):                                 # column tile iG + g
        return jnp.minimum(i * G + g, nblk - 1)

    def diag(g):
        return pl.BlockSpec((None, None, B, B),
                            lambda k, i, *_: (k, col(i, g), 0, 0))

    grp = pl.BlockSpec((None, None, G, B), lambda k, i, *_: (k, i, 0, 0))
    cd_specs = [diag(g) for g in range(G)] + [grp] * len(slabs)
    cd_args = (*[qt_blocks] * G, *slabs)
    out_shape = [
        jax.ShapeDtypeStruct((K, ngrp * G, 2, B), alphas.dtype),
        jax.ShapeDtypeStruct((K, nblk, B), us.dtype),
        jax.ShapeDtypeStruct((K, ngrp, G, 1), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((None, G, 2, B), lambda k, i, *_: (k, i, 0, 0)),  # a'
        pl.BlockSpec((None, nblk, B), lambda k, *_: (k, 0, 0)),      # u_d
        pl.BlockSpec((None, None, G, 1), lambda k, i, *_: (k, i, 0, 0)),
    ]
    if isinstance(src, gram_mod.DenseSource):
        kernel = functools.partial(_fused_dense_kernel, nblk=nblk, cd=cd)
        a, ud, s = pl.pallas_call(
            kernel,
            grid=(K, ngrp, G, nblk),
            in_specs=cd_specs + [
                pl.BlockSpec((1, B, B),
                             lambda k, i, g, j: (k, j, col(i, g))),   # Q
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[gram_mod._scratch((G, B, 1))],
            interpret=interpret,
        )(*cd_args, src.q)
    else:
        bd = src.bd
        n_d = src.x.shape[-1] // bd
        xx = gram_mod.row_norms(src.x)[:, None, :].astype(src.x.dtype)
        kernel = functools.partial(_fused_mf_kernel, kind=src.kind,
                                   gamma=src.gamma, degree=src.degree,
                                   coef0=src.coef0, n_d=n_d, nblk=nblk,
                                   cd=cd)
        at_i = pl.BlockSpec((None, 1, B),
                            lambda k, i, g, j, d: (k, 0, col(i, g)))
        at_j = pl.BlockSpec((None, 1, B), lambda k, i, g, j, d: (k, 0, j))
        a, ud, s = pl.pallas_call(
            kernel,
            grid=(K, ngrp, G, nblk, n_d),
            in_specs=cd_specs + [
                grp, at_j,                                       # y_i, y_j
                at_j, at_i,                                      # xx_j, xx_i
                pl.BlockSpec((None, B, bd),
                             lambda k, i, g, j, d: (k, j, d)),   # x_j
                pl.BlockSpec((None, B, bd),
                             lambda k, i, g, j, d: (k, col(i, g), d)),  # x_i
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[gram_mod._scratch((B, B)),
                            gram_mod._scratch((G, B, 1))],
            interpret=interpret,
        )(*cd_args, _grouped(src.y.reshape(K, nblk, B), G),
          src.y[:, None, :], xx, xx, src.x, src.x)
    return (a[:, :nblk].reshape(K, nblk, 2 * B), ud.reshape(K, m),
            s.reshape(K, ngrp * G)[:, :nblk])


# ---------------------------------------------------------------------------
# level solve: fused pass loop + exact line search + exact KKT stop
# ---------------------------------------------------------------------------

def solve_level(q_blocks: Array, src, alphas0: Array, *, c: float,
                ups: float, theta: float, mscale: float,
                steps_per_pass: int | None = None, n_passes: int = 30,
                tol: float = 1e-5, valid: Array | None = None,
                us0: Array | None = None, adaptive: bool = True,
                fused: bool | None = None,
                interpret: bool = False
                ) -> tuple[Array, Array, Array, Array]:
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

    Returns (alphas (K, 2m), kkts (K,), passes (), counts (K, 2) int32:
    each partition's :func:`step_counts` summed over the passes).
    """
    K, nblk, B, _ = q_blocks.shape
    m = nblk * B
    G = group_size(alphas0.dtype)
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
        alphas, us, _, it, counts = carry
        zetas, betas = alphas[:, :m], alphas[:, m:]
        a_t = jnp.concatenate([zetas.reshape(K, nblk, B),
                               betas.reshape(K, nblk, B)], axis=2)
        if fused:
            a_t, u_d, steps = fused_cd_pass(
                q_blocks, src, a_t, us.reshape(K, nblk, B), valids, c=c,
                ups=ups, theta=theta, mscale=mscale, n_steps=n_steps,
                exit_tol=exit_tol, interpret=interpret)
        else:
            # two-launch layout: same sweep helper, same math — the Gram
            # matvec just rides a second launch (src.matvec) instead of
            # accumulating inside the sweep kernel
            a_t, _, steps = cd_block_sweep(
                q_blocks, a_t, us.reshape(K, nblk, B), c=c, ups=ups,
                theta=theta, mscale=mscale, n_steps=n_steps,
                exit_tol=exit_tol, valids=valids, interpret=interpret)
        z_new = a_t[:, :, :B].reshape(K, m)
        b_new = a_t[:, :, B:].reshape(K, m)
        dz, db = z_new - zetas, b_new - betas
        if not fused:
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
        return (alphas, us, kkt(alphas, us), it + 1,
                counts + step_counts(steps, G))

    def cond(carry):
        _, _, r, it, _ = carry
        return jnp.logical_and(it < n_passes, jnp.max(r) > tol)

    if us0 is None:
        zetas0, betas0 = alphas0[:, :m], alphas0[:, m:]
        us0 = src.matvec(zetas0 - betas0)
    init = (alphas0, us0, kkt(alphas0, us0), jnp.int32(0),
            jnp.zeros((K, 2), jnp.int32))
    alphas, _, r, it, counts = jax.lax.while_loop(cond, body, init)
    return alphas, r, it, counts


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
    alphas, r, it, _ = solve_level(
        qb, gram_mod.DenseSource(Q[None]), a0[None], c=c, ups=ups,
        theta=theta, mscale=mscale, steps_per_pass=steps_per_pass,
        n_passes=n_passes, tol=tol, valid=valid, adaptive=adaptive,
        fused=fused, interpret=interpret)
    return alphas[0], r[0], it
