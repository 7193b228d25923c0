"""Matrix-free multi-kernel Gram subsystem: tiled Pallas Gram/matvec kernels.

Lowers every ``KernelSpec`` family from the ODM paper (Zhang & Zhou, 2016)
— ``rbf``, ``laplacian``, ``poly``, ``linear`` — to tiled TPU kernels that
share ONE accumulation skeleton (:func:`accum_tile` / :func:`finalize_tile`):

* **L2 family** (``rbf``, ``poly``, ``linear``): the pairwise cross term
  ``x @ z.T`` is accumulated over feature blocks on the MXU
  (``dot_general`` with an fp32 scratch accumulator); the kernel transform
  (``exp``, integer power, identity) runs on the VPU over the finished
  tile. Squared row norms for rbf are precomputed on host (O(Md),
  negligible) and streamed as (1, bm)-shaped scalars-per-row.

* **L1 family** (``laplacian``): there is no matmul form of the L1
  distance, so the tile is built by a tiled VPU reduction — an unrolled
  loop over the features, each contributing a (bm, bn) slab
  ``|x_id - z_jd|`` (an x column against a row of the transposed z tile),
  summed ``_L1_CHUNK`` features at a time. Peak extra VMEM is two
  (bm, bn) fp32 slabs, so laplacian tiles respect the same budget as the
  MXU path at the default blocks.

Three consumers share the skeleton:

1. :func:`gram`        — materialize a (signed) Gram tile grid, (M, N).
2. :func:`gram_matvec` — batched u[k] = K_k @ g[k] with no (M, N) Gram
   ever leaving VMEM (O(m*B) memory per partition however large the full
   Gram would be).
3. ``repro.kernels.dual_cd_block``'s fused CD pass — the same tile
   accumulation feeding an in-kernel accumulating matvec, one
   ``pallas_call`` per solver pass.

VMEM budget per grid step (fp32):
  L2 gram:    bm*bd + bn*bd (operands) + bm*bn (acc); defaults
              (256, 256, 512) => 1 MB + 0.25 MB — far under the ~16 MB/core
              budget, leaving room for double buffering.
  L1 gram:    bm*bd + bn*bd + bm*bn + 2*bm*bn transient => ~1.8 MB at
              the same defaults.
  matvec:     the gram-step budget + bn (g tile) + bm (u accumulator).

``gram_threshold`` semantics (see ``SODMConfig``): SODM level solves with
partition size m <= gram_threshold materialize the O(m^2) signed Gram once
(cheaper when it fits — tiles are reused every pass); above the threshold
all four kernel families switch to these matrix-free tiles, so per-level
memory stays O(m*B) and the threshold is purely a speed/memory trade, not
a capability cliff. :data:`MATRIX_FREE_KERNELS` lists the families with a
matrix-free lowering; ``repro.core.engines`` warns (once, with a memory
estimate) if any other kernel is asked to solve above the threshold.

MXU alignment: bm, bn, bd multiples of 128 on real TPUs (the ops.py
wrappers pad); the D sweep is the innermost grid axis so the fp32
accumulator scratch lives across it and each output tile is written once.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.precision import MATMUL

Array = jax.Array

# kernel families with a matrix-free tile lowering (all of KernelSpec's);
# L1_KERNELS is the single source of the l1-vs-l2 accumulation split —
# KernelSpec.family() and the tile skeleton both dispatch on it
MATRIX_FREE_KERNELS = ("linear", "rbf", "laplacian", "poly")
L1_KERNELS = ("laplacian",)

# features summed per partial of the laplacian L1 reduction before it is
# added to the accumulator (fixes the floating-point summation order)
_L1_CHUNK = 8


# ---------------------------------------------------------------------------
# the shared accumulation skeleton
# ---------------------------------------------------------------------------

def accum_tile(kind: str, acc: Array, x: Array, z: Array) -> Array:
    """acc (bm, bn) += one feature slab's pairwise contribution.

    L2 family: the ``x @ z.T`` cross term on the MXU. L1 family
    (laplacian): partial L1 distance via per-feature VPU slabs. ``kind``
    is static, so each kernel compiles exactly one of the two paths.
    """
    if kind in L1_KERNELS:
        bd = x.shape[-1]
        xf = x.astype(jnp.float32)
        zt = z.astype(jnp.float32).T                   # (bd, bn)
        # static per-feature (bm, bn) slabs, summed chunk by chunk: Mosaic
        # lowers neither the dynamic_slice of a fori_loop over chunks nor
        # a (bm, bn, chunk) broadcast within its VMEM budget
        for lo in range(0, bd, _L1_CHUNK):
            part = None
            for f in range(lo, min(lo + _L1_CHUNK, bd)):
                t = jnp.abs(xf[:, f:f + 1] - zt[f:f + 1, :])
                part = t if part is None else part + t
            acc = acc + part
        return acc
    return acc + jax.lax.dot_general(
        x, z, (((1,), (1,)), ((), ())), precision=MATMUL,
        preferred_element_type=jnp.float32)


def finalize_tile(kind: str, acc: Array, xx: Array, zz: Array, *,
                  gamma: float, degree: int, coef0: float) -> Array:
    """Finished accumulator -> kernel tile, on the VPU.

    ``acc`` is the L2 cross term (L2 family) or the full L1 distance
    (laplacian). ``xx``/``zz`` are the (bm,)/(bn,) squared row norms —
    only rbf reads them; the others accept them for a uniform signature.
    """
    if kind == "rbf":
        d2 = xx[:, None] + zz[None, :] - 2.0 * acc
        return jnp.exp(-gamma * jnp.maximum(d2, 0.0))
    if kind == "laplacian":
        return jnp.exp(-gamma * acc)
    if kind == "poly":
        return (gamma * acc + coef0) ** degree
    if kind == "linear":
        return acc
    raise ValueError(f"no matrix-free lowering for kernel {kind!r}; "
                     f"supported: {MATRIX_FREE_KERNELS}")


def row_norms(x: Array) -> Array:
    """Squared L2 row norms in fp32, batched over leading axes."""
    return jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)


# ---------------------------------------------------------------------------
# gram: (M, N) tile grid
# ---------------------------------------------------------------------------

def _gram_kernel(xx_ref, zz_ref, yx_ref, yz_ref, x_ref, z_ref, out_ref,
                 acc_ref, *, kind: str, gamma: float, degree: int,
                 coef0: float, signed: bool, n_d_steps: int):
    """One (bm, bn) tile, accumulating over D blocks (innermost grid axis).

    xx/zz: (1, bm)/(1, bn) squared row norms; yx/yz: labels (only read when
    signed). x (bm, bd), z (bn, bd). acc: (bm, bn) fp32 scratch.
    """
    kd = pl.program_id(2)

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = accum_tile(kind, acc_ref[...], x_ref[...], z_ref[...])

    @pl.when(kd == n_d_steps - 1)
    def _finalize():
        k = finalize_tile(kind, acc_ref[...], xx_ref[0, :], zz_ref[0, :],
                          gamma=gamma, degree=degree, coef0=coef0)
        if signed:
            k = (yx_ref[0, :][:, None] * yz_ref[0, :][None, :]) * k
        out_ref[...] = k.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "gamma", "degree", "coef0", "signed", "bm", "bn", "bd",
    "interpret"))
def gram(x: Array, z: Array, yx: Array | None = None,
         yz: Array | None = None, *, kind: str = "rbf", gamma: float = 1.0,
         degree: int = 3, coef0: float = 1.0, signed: bool = False,
         bm: int = 256, bn: int = 256, bd: int = 512,
         interpret: bool = False) -> Array:
    """K (or Q if signed) of shape (M, N) for any supported kernel family.

    Shapes must tile evenly; the ops.py wrapper pads and unpads arbitrary
    shapes. Grid (M/bm, N/bn, D/bd) with D innermost (see module docs).
    """
    M, D = x.shape
    N = z.shape[0]
    assert M % bm == 0 and N % bn == 0 and D % bd == 0, (M, N, D, bm, bn, bd)
    if yx is None:
        yx = jnp.ones((M,), x.dtype)
    if yz is None:
        yz = jnp.ones((N,), x.dtype)
    n_d_steps = D // bd

    grid = (M // bm, N // bn, n_d_steps)
    xx = row_norms(x)[None, :]                                   # (1, M)
    zz = row_norms(z)[None, :]                                   # (1, N)

    kernel = functools.partial(_gram_kernel, kind=kind, gamma=gamma,
                               degree=degree, coef0=coef0, signed=signed,
                               n_d_steps=n_d_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm), lambda i, j, k: (0, i)),       # xx
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),       # zz
            pl.BlockSpec((1, bm), lambda i, j, k: (0, i)),       # yx
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),       # yz
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),      # x
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),      # z
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[_scratch((bm, bn))],
        interpret=interpret,
    )(xx, zz, yx[None, :], yz[None, :], x, z)


# ---------------------------------------------------------------------------
# gram_matvec: batched u = K @ g, tile never leaves VMEM
# ---------------------------------------------------------------------------

def _gram_matvec_kernel(xx_ref, zz_ref, g_ref, x_ref, z_ref, out_ref,
                        acc_ref, u_ref, *, kind: str, gamma: float,
                        degree: int, coef0: float, n_j: int, n_d: int):
    """One (bm,) slice of u = K(x, z) @ g, accumulated over (j, d) tiles.

    Grid (K, M/bm, N/bn, D/bd). The (bm, bn) Gram tile is formed in the
    acc scratch across the D sweep exactly like :func:`_gram_kernel`, then
    immediately contracted against the matching g tile into the (bm, 1)
    u scratch — the tile never leaves VMEM, so memory stays O(m·B) however
    large the partition's full Gram would be.
    """
    kj = pl.program_id(2)
    kd = pl.program_id(3)

    @pl.when(jnp.logical_and(kj == 0, kd == 0))
    def _init_u():
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(kd == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = accum_tile(kind, acc_ref[...], x_ref[0], z_ref[0])

    @pl.when(kd == n_d - 1)
    def _contract():
        k = finalize_tile(kind, acc_ref[...], xx_ref[0, 0, :],
                          zz_ref[0, 0, :], gamma=gamma, degree=degree,
                          coef0=coef0)
        g = g_ref[0, 0, :]                     # (bn,)
        u_ref[...] += jax.lax.dot_general(     # (bm, bn) @ (bn, 1)
            k, g[:, None], (((1,), (0,)), ((), ())), precision=MATMUL,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(kj == n_j - 1, kd == n_d - 1))
    def _finalize():
        out_ref[...] = u_ref[...].astype(out_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=(
    "kind", "gamma", "degree", "coef0", "bm", "bn", "bd", "interpret"))
def gram_matvec(x: Array, z: Array, g: Array, *, kind: str = "rbf",
                gamma: float = 1.0, degree: int = 3, coef0: float = 1.0,
                bm: int = 256, bn: int = 256, bd: int = 512,
                interpret: bool = False) -> Array:
    """u[k] = K(x[k], z[k]) @ g[k] without materializing any (M, N) Gram.

    Batched over a leading partition axis so one SODM level's u refresh is
    a single pallas_call: x (K, M, D), z (K, N, D), g (K, N) -> u (K, M).
    Shapes must tile evenly; the ops.py wrapper pads arbitrary shapes. For
    the *signed* product Q @ g = y ⊙ (K @ (y ⊙ g)) fold the labels into g
    and the result (the ops wrapper does).
    """
    K, M, D = x.shape
    N = z.shape[1]
    assert M % bm == 0 and N % bn == 0 and D % bd == 0, (M, N, D, bm, bn, bd)
    n_j, n_d = N // bn, D // bd
    grid = (K, M // bm, n_j, n_d)
    xx = row_norms(x)[:, None, :]                               # (K, 1, M)
    zz = row_norms(z)[:, None, :]                               # (K, 1, N)

    kernel = functools.partial(_gram_matvec_kernel, kind=kind, gamma=gamma,
                               degree=degree, coef0=coef0, n_j=n_j, n_d=n_d)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm), lambda k, i, j, d: (k, 0, i)),   # xx
            pl.BlockSpec((1, 1, bn), lambda k, i, j, d: (k, 0, j)),   # zz
            pl.BlockSpec((1, 1, bn), lambda k, i, j, d: (k, 0, j)),   # g
            pl.BlockSpec((1, bm, bd), lambda k, i, j, d: (k, i, d)),  # x
            pl.BlockSpec((1, bn, bd), lambda k, i, j, d: (k, j, d)),  # z
        ],
        out_specs=pl.BlockSpec((1, bm, 1), lambda k, i, j, d: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, M, 1), x.dtype),
        scratch_shapes=[_scratch((bm, bn)), _scratch((bm, 1))],
        interpret=interpret,
    )(xx, zz, g[:, None, :], x, z)
    return out[:, :, 0]


# ---------------------------------------------------------------------------
# gram sources: how a solver pass reaches the off-diagonal mass
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DenseSource:
    """Materialized signed Gram Q (K, mp, mp) — below ``gram_threshold``.

    Padded rows/columns must already be masked to zero. ``matvec`` is a
    plain batched matmul; the fused CD pass streams (B, B) tiles of ``q``
    straight from HBM.
    """

    q: Array                   # (K, mp, mp) signed, padding masked

    def matvec(self, g: Array) -> Array:
        return jnp.einsum("kij,kj->ki", self.q, g)


@dataclasses.dataclass
class KernelSource:
    """On-the-fly Gram tiles from the raw features — above ``gram_threshold``.

    ``x`` (K, mp, Dp) is row- and feature-padded (pads zero); ``y`` (K, mp)
    carries 0 labels on padded rows so the signed product
    y ⊙ (K @ (y ⊙ g)) zeroes padded rows and columns without ever masking
    a Gram tile. ``kind``/``gamma``/``degree``/``coef0`` mirror KernelSpec.
    """

    kind: str
    x: Array                   # (K, mp, Dp)
    y: Array                   # (K, mp), 0.0 on padded rows
    gamma: float = 1.0
    degree: int = 3
    coef0: float = 1.0
    bm: int = 256
    bn: int = 256
    bd: int = 512
    interpret: bool = False

    def matvec(self, g: Array) -> Array:
        u = gram_matvec(self.x, self.x, self.y * g, kind=self.kind,
                        gamma=self.gamma, degree=self.degree,
                        coef0=self.coef0, bm=self.bm, bn=self.bn,
                        bd=self.bd, interpret=self.interpret)
        return self.y * u


def make_kernel_source(spec, x: Array, y: Array, *, bm: int, bn: int,
                       bd: int = 512, interpret: bool = False
                       ) -> KernelSource:
    """Build a :class:`KernelSource` from a KernelSpec-like object.

    ``x`` (K, mp, D) must already be row-padded to the tile multiple; the
    feature axis is padded here (zero features shift no distance and no
    inner product). ``spec`` is duck-typed (name/gamma/degree/coef0) so
    this module never imports repro.core.
    """
    if spec.name not in MATRIX_FREE_KERNELS:
        raise ValueError(f"no matrix-free lowering for {spec.name!r}")
    D = x.shape[-1]
    bd = min(bd, max(8, D))
    target = -(-D // bd) * bd
    if target != D:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, target - D)))
    return KernelSource(kind=spec.name, x=x, y=y, gamma=spec.gamma,
                        degree=spec.degree, coef0=spec.coef0, bm=bm, bn=bn,
                        bd=bd, interpret=interpret)


def _scratch(shape: tuple[int, ...]):
    try:
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.VMEM(shape, jnp.float32)
    except Exception:                          # pragma: no cover
        return pl.VMEM(shape, jnp.float32)
