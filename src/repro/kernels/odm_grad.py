"""Pallas TPU kernels: fused linear-kernel primal ODM gradients.

Two fused passes share the layout:

* :func:`odm_grad` — full-batch anchor gradient

      grad p(w) = w + s · Xᵀ[(lo + ups·hi) ⊙ y],  s = lam / (M (1-θ)²)

  where lo/hi are the two-sided margin residuals (Section 3.3). XLA
  lowers the naive expression as two passes over X (one for the margins
  X w, one for the back-projection Xᵀ coef). For DSVRG the gradient is
  the hot spot and X is the dominant operand, so fusing both matvecs into
  a single HBM pass halves the memory traffic — the op is memory-bound
  (arithmetic intensity ≈ 2 flops/byte either way), so that is a ~2× win.

* :func:`odm_svrg_grad` — the DSVRG inner-step direction

      g_w − g_a + h = (w − a + h) + Xᵀ[(coef_w − coef_a) ⊙ wt] / n_valid

  The naive form is FOUR passes over the minibatch (margins + back-
  projection for each of w and the anchor a); the fused kernel loads each
  X slab once, computes BOTH margin products as a single (bm, 2) MXU op
  against the stacked [w; a] (the ``gram.py`` accumulation skeleton's
  cross-term, :func:`repro.kernels.gram.accum_tile`), forms the
  coefficient difference on the VPU, and back-projects — a ~4× traffic
  cut on the epoch's dominant operand. ``wt`` masks ragged-tail padding
  rows; ``inv_n`` (host-precomputed 1/n_valid) keeps the mean exact for
  partial tails.

Tiling (both): grid (M/bm,), sequential on TPU, so all cells accumulate
into the same (1, d) output block; cell i loads its (bm, d) X slab once,
cell 0 initializes the accumulator with the ridge/variance-reduction term.
VMEM: bm·d + O(d) + O(bm) floats; defaults (bm=512, d≤8192) ≈ 16 MB fp32
upper bound — the ops.py wrappers halve bm when bm·d would exceed the
budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gram import accum_tile
from repro.precision import MATMUL

Array = jax.Array


def _row_tile(bm: int):
    """BlockSpec of one (1, bm) row tile of a per-row vector laid out as
    (M/bm, 1, bm): the block's last two dims equal the array's, so any bm
    meets the TPU block-shape rule (a (1, bm) block of a (1, M) row would
    need bm % 128 == 0, which the wide-feature bm shrink breaks)."""
    return pl.BlockSpec((None, 1, bm), lambda i: (i, 0, 0))


def _row_tiles(v: Array, bm: int) -> Array:
    return v.reshape(-1, 1, bm)


def _hinge_coef(m: Array, y: Array, *, s: float, theta: float,
                ups: float) -> Array:
    """VPU per-instance coefficient s·(lo + ups·hi)·y (odm._hinge_coef)."""
    lo = jnp.where(m < 1.0 - theta, m + theta - 1.0, 0.0)
    hi = jnp.where(m > 1.0 + theta, m - theta - 1.0, 0.0)
    return s * (lo + ups * hi) * y


def _odm_grad_kernel(w_ref, x_ref, y_ref, out_ref, *, s: float, theta: float,
                     ups: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = w_ref[...]

    x = x_ref[...]                              # (bm, d)
    w = w_ref[0, :]                             # (d,)
    y = y_ref[0, :]                             # (bm,)
    m = y * jax.lax.dot_general(x, w[:, None], (((1,), (0,)), ((), ())),
                                precision=MATMUL,
                                preferred_element_type=jnp.float32)[:, 0]
    coef = _hinge_coef(m, y, s=s, theta=theta, ups=ups).astype(x.dtype)
    part = jax.lax.dot_general(coef[None, :], x, (((1,), (0,)), ((), ())),
                               precision=MATMUL,
                               preferred_element_type=jnp.float32)  # (1, d)
    out_ref[...] += part.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lam", "theta", "ups", "bm",
                                             "interpret"))
def odm_grad(w: Array, x: Array, y: Array, *, lam: float = 1.0,
             theta: float = 0.1, ups: float = 0.5, bm: int = 512,
             interpret: bool = False) -> Array:
    """Full-batch grad p(w). Shapes must tile evenly (ops.py pads)."""
    M, d = x.shape
    assert M % bm == 0, (M, bm)
    s = lam / (M * (1.0 - theta) ** 2)
    kernel = functools.partial(_odm_grad_kernel, s=s, theta=theta, ups=ups)
    out = pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),      # w
            pl.BlockSpec((bm, d), lambda i: (i, 0)),     # x
            _row_tile(bm),                               # y
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), w.dtype),
        interpret=interpret,
    )(w[None, :], x, _row_tiles(y, bm))
    return out[0]


# ---------------------------------------------------------------------------
# fused DSVRG inner-step direction
# ---------------------------------------------------------------------------

def _svrg_grad_kernel(wa_ref, h_ref, inv_ref, x_ref, y_ref, wt_ref, out_ref,
                      *, s: float, theta: float, ups: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # variance-reduction base (w - a + h): ridge terms of g_w - g_a
        # cancel to w - a, then the anchor full gradient h rides on top
        out_ref[...] = (wa_ref[0, :] - wa_ref[1, :] + h_ref[0, :])[None, :]

    x = x_ref[...]                              # (bm, d)
    y = y_ref[0, :]                             # (bm,)
    # both margin products in ONE MXU op: x @ [w; a]ᵀ via the shared Gram
    # cross-term skeleton -> (bm, 2) columns [x·w, x·a]
    mm = y[:, None] * accum_tile(
        "linear", jnp.zeros((x.shape[0], 2), jnp.float32), x, wa_ref[...])
    dcoef = _hinge_coef(mm[:, 0], y, s=s, theta=theta, ups=ups) \
        - _hinge_coef(mm[:, 1], y, s=s, theta=theta, ups=ups)
    dcoef = (dcoef * wt_ref[0, :] * inv_ref[0, 0]).astype(x.dtype)  # (bm,)
    part = jax.lax.dot_general(dcoef[None, :], x, (((1,), (0,)), ((), ())),
                               precision=MATMUL,
                               preferred_element_type=jnp.float32)  # (1, d)
    out_ref[...] += part.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("s", "theta", "ups", "bm",
                                             "interpret"))
def odm_svrg_grad(w: Array, anchor: Array, h: Array, x: Array, y: Array,
                  wt: Array, inv_n: Array, *, s: float, theta: float = 0.1,
                  ups: float = 0.5, bm: int = 512,
                  interpret: bool = False) -> Array:
    """Fused g_w − g_a + h on one (possibly masked) minibatch.

    x (B, d) with B % bm == 0 (ops.py pads); wt (B,) 1.0 on real rows and
    0.0 on padding; inv_n a (1, 1) array holding 1/n_valid (host-side, so
    the masked mean stays exact whatever the tail size). ``s`` is the
    per-instance hinge scale lam/(1-θ)² — no 1/M, the division is inv_n.
    """
    B, d = x.shape
    assert B % bm == 0, (B, bm)
    kernel = functools.partial(_svrg_grad_kernel, s=s, theta=theta, ups=ups)
    out = pl.pallas_call(
        kernel,
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((2, d), lambda i: (0, 0)),      # [w; anchor]
            pl.BlockSpec((1, d), lambda i: (0, 0)),      # h
            pl.BlockSpec((1, 1), lambda i: (0, 0)),      # 1/n_valid
            pl.BlockSpec((bm, d), lambda i: (i, 0)),     # x
            _row_tile(bm),                               # y
            _row_tile(bm),                               # wt
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), w.dtype),
        interpret=interpret,
    )(jnp.stack([w, anchor]), h[None, :], inv_n, x, _row_tiles(y, bm),
      _row_tiles(wt, bm))
    return out[0]
