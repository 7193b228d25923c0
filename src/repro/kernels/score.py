"""Tiled matrix-free decision-function (serving) kernel.

Inference for a kernel expansion is  f(x_t) = sum_s coef_s kappa(x_s, x_t)
— a Gram-times-vector product against the packed support-vector slab. The
seed-era path materialized the dense (T, S) test Gram for every predict
call; this kernel reuses the :mod:`repro.kernels.gram` accumulation
skeleton (:func:`accum_tile` / :func:`finalize_tile`: MXU cross term for
the L2 family, chunked VPU L1 reduction for laplacian) to contract each
(bt, bs) kernel tile against its coef tile *inside VMEM*, so one request
batch is ONE ``pallas_call`` and peak memory is O(B·S_block) — the (T, S)
Gram never exists, however many support vectors the model keeps.

Three entry points:

* :func:`score_tiles`   — the Pallas kernel (tile-aligned shapes; the
  ops.py wrapper pads arbitrary shapes).
* :func:`score_ref`     — dense pure-jnp oracle (materializes (T, S));
  the parity target of the kernel tests, exactly like ``odm_grad``'s
  reference.
* :func:`score_blocked` — jnp row-block streaming fallback used under
  interpret mode (CPU hosts), where unrolling the (T/bt)·(S/bs) grid into
  the trace would bloat compile time: a ``lax.map`` over (bt, d) request
  chunks keeps the same O(bt·S) memory bound at XLA speed.

Grid (T/bt, S/bs, D/bd), D innermost so the fp32 cross-term accumulator
scratch lives across the feature sweep; the (bt, 1) score accumulator
lives across the S sweep. VMEM per step (fp32, defaults bt=bs=256,
bd=512): operands bt·bd + bs·bd = 1 MB, acc bt·bs = 0.25 MB, scores bt —
same budget as the gram matvec kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gram import (accum_tile, finalize_tile, row_norms,
                                _scratch, L1_KERNELS)
from repro.precision import MATMUL, matmul

Array = jax.Array


def _score_kernel(xx_ref, zz_ref, c_ref, x_ref, z_ref, out_ref, acc_ref,
                  u_ref, *, kind: str, gamma: float, degree: int,
                  coef0: float, n_j: int, n_d: int):
    """One (bt,) slice of f = K(x, z) @ coef, accumulated over (j, d) tiles.

    x (bt, bd) request rows, z (bs, bd) SV rows, c (1, bs) coef tile.
    acc (bt, bs) fp32 Gram-tile scratch (across the D sweep), u (bt, 1)
    fp32 score scratch (across the S sweep). The kernel tile is contracted
    against the coef tile the moment it is finished — it never leaves VMEM.
    """
    kj = pl.program_id(1)
    kd = pl.program_id(2)

    @pl.when(jnp.logical_and(kj == 0, kd == 0))
    def _init_u():
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(kd == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = accum_tile(kind, acc_ref[...], x_ref[...], z_ref[...])

    @pl.when(kd == n_d - 1)
    def _contract():
        k = finalize_tile(kind, acc_ref[...], xx_ref[0, :], zz_ref[0, :],
                          gamma=gamma, degree=degree, coef0=coef0)
        c = c_ref[0, :]                        # (bs,)
        u_ref[...] += jax.lax.dot_general(     # (bt, bs) @ (bs, 1)
            k, c[:, None], (((1,), (0,)), ((), ())), precision=MATMUL,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(kj == n_j - 1, kd == n_d - 1))
    def _finalize():
        out_ref[...] = u_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "gamma", "degree", "coef0", "bt", "bs", "bd", "interpret"))
def score_tiles(x: Array, z: Array, coef: Array, *, kind: str = "rbf",
                gamma: float = 1.0, degree: int = 3, coef0: float = 1.0,
                bt: int = 256, bs: int = 256, bd: int = 512,
                interpret: bool = False) -> Array:
    """f (T,) = K(x, z) @ coef in ONE pallas_call; shapes must tile evenly
    (the ops.py wrapper pads — padded coef entries are zero so padded SV
    rows contribute nothing, padded request rows are sliced off)."""
    T, D = x.shape
    S = z.shape[0]
    assert T % bt == 0 and S % bs == 0 and D % bd == 0, (T, S, D, bt, bs, bd)
    n_j, n_d = S // bs, D // bd
    grid = (T // bt, n_j, n_d)
    xx = row_norms(x)[None, :]                                  # (1, T)
    zz = row_norms(z)[None, :]                                  # (1, S)

    kernel = functools.partial(_score_kernel, kind=kind, gamma=gamma,
                               degree=degree, coef0=coef0, n_j=n_j, n_d=n_d)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt), lambda i, j, d: (0, i)),      # xx
            pl.BlockSpec((1, bs), lambda i, j, d: (0, j)),      # zz
            pl.BlockSpec((1, bs), lambda i, j, d: (0, j)),      # coef
            pl.BlockSpec((bt, bd), lambda i, j, d: (i, d)),     # x
            pl.BlockSpec((bs, bd), lambda i, j, d: (j, d)),     # z
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i, j, d: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 1), x.dtype),
        scratch_shapes=[_scratch((bt, bs)), _scratch((bt, 1))],
        interpret=interpret,
    )(xx, zz, coef[None, :], x, z)
    return out[:, 0]


# ---------------------------------------------------------------------------
# pure-jnp oracle + streaming fallback
# ---------------------------------------------------------------------------

def _dense_gram(x: Array, z: Array, *, kind: str, gamma: float, degree: int,
                coef0: float) -> Array:
    """Dense (T, S) kernel block via the same accumulate/finalize math."""
    if kind in L1_KERNELS:
        acc = jnp.sum(jnp.abs(x[:, None, :] - z[None, :, :]), axis=-1)
    else:
        acc = matmul(x.astype(jnp.float32), z.astype(jnp.float32).T)
    return finalize_tile(kind, acc, row_norms(x), row_norms(z),
                         gamma=gamma, degree=degree, coef0=coef0)


def score_ref(x: Array, z: Array, coef: Array, *, kind: str = "rbf",
              gamma: float = 1.0, degree: int = 3,
              coef0: float = 1.0) -> Array:
    """Dense oracle: materializes the (T, S) block. Parity target only —
    production paths go through :func:`score_tiles` / :func:`score_blocked`."""
    k = _dense_gram(x, z, kind=kind, gamma=gamma, degree=degree, coef0=coef0)
    return matmul(k, coef.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "gamma", "degree", "coef0", "bt"))
def score_blocked(x: Array, z: Array, coef: Array, *, kind: str = "rbf",
                  gamma: float = 1.0, degree: int = 3, coef0: float = 1.0,
                  bt: int = 256) -> Array:
    """Streaming jnp scorer: lax.map over (bt, d) request chunks.

    Numerically identical to :func:`score_ref` but peak memory is
    O(bt · S) — one kernel block per chunk, never the full (T, S). The
    interpret-mode (CPU) production path; T must be a bt multiple (the
    ops.py wrapper pads).
    """
    T, D = x.shape
    assert T % bt == 0, (T, bt)
    chunks = x.reshape(T // bt, bt, D)

    def one(xc):
        k = _dense_gram(xc, z, kind=kind, gamma=gamma, degree=degree,
                        coef0=coef0)
        return matmul(k, coef.astype(jnp.float32)).astype(x.dtype)

    return jax.lax.map(one, chunks).reshape(T)
