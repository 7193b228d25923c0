"""Static VMEM / tiling checks for every registered Pallas kernel.

A Pallas TPU kernel's per-tile memory is decided entirely by its launch
plan: block shapes x dtype for every BlockSpec operand, plus scratch.
When the plan doesn't fit VMEM the failure today is a Mosaic compile
error deep inside the fused pass — most famously the partition-resident
``(nblk, B)`` ``u_d`` block of
:func:`repro.kernels.dual_cd_block.fused_cd_pass`, whose 4·m bytes cross
the ceiling near m = 2·10⁶ (ROADMAP open item 1).
This module makes that failure a *plan-time* :class:`PallasBudgetError`
with a per-block sizing report instead.

It also applies the TPU lowering's block-shape rule: the last two dims of
every BlockSpec block must be divisible by (8, 128) or equal the array's
own dims (:func:`tiling_problems`). Interpret mode never checks it, so a
plan that breaks it passes every CPU test and is refused only by the
chip's compiler.

Model: a TPU core has ~16 MiB of VMEM (see the Pallas guide). Mosaic
double-buffers streamed blocks to overlap DMA with compute, so we charge
the **single-copy footprint** (streams + residents + scratch) against
**half** the physical VMEM, reserving the other half for the pipeline's
second copies. That is deliberately conservative-but-simple: a plan that
fits half-VMEM single-copy always has room to double-buffer its streams.

Each kernel registers a *plan builder* in :data:`PLAN_BUILDERS` that
mirrors its real BlockSpecs (shapes are asserted against the kernel
modules' constants where possible, so a kernel refactor that changes
block shapes breaks the mirror loudly in tests, not silently).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from repro.analysis.jaxpr_lint import InvariantViolation

__all__ = [
    "Block", "KernelPlan", "PallasBudgetError", "VMEM_BYTES",
    "vmem_budget", "sizing_report", "check_plan", "tiling_problems",
    "PLAN_BUILDERS",
    "default_plans", "check_kernels",
    "gram_plan", "gram_matvec_plan", "fused_cd_plan", "score_plan",
    "odm_grad_plan", "svrg_grad_plan",
]

#: physical VMEM per core, by backend
VMEM_BYTES = {"tpu": 16 * 2 ** 20}

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
                "int8": 1, "bool": 1}


def vmem_budget(backend: str = "tpu") -> int:
    """Usable single-copy budget: half the physical VMEM (the other half
    is reserved for Mosaic's double-buffered stream copies)."""
    return VMEM_BYTES[backend] // 2


class PallasBudgetError(InvariantViolation):
    """A kernel launch plan exceeds the static VMEM budget (or violates a
    tiling assumption). The message carries the full sizing report."""


@dataclasses.dataclass(frozen=True)
class Block:
    """One VMEM-resident array in a kernel plan.

    kind:
      * ``stream``   — re-fetched per grid step (a BlockSpec with a
        grid-dependent index_map); Mosaic double-buffers these.
      * ``resident`` — same block across grid steps (constant index_map),
        e.g. the fused pass's partition-wide ``u_d`` and label rows, or
        ``odm_grad``'s full ``w``/``out`` slabs.
      * ``scratch``  — ``pltpu.VMEM`` scratch allocated for the launch.

    ``array`` is the shape of the whole operand a BlockSpec tiles (same
    rank as ``shape``, squeezed dims given as 1); it feeds the block-shape
    rule. ``None`` for scratch and in-kernel transients.
    """

    name: str
    shape: tuple[int, ...]
    dtype: str = "float32"
    kind: str = "stream"
    array: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.kind not in ("stream", "resident", "scratch"):
            raise ValueError(f"unknown block kind {self.kind!r}")
        if self.dtype not in _DTYPE_BYTES:
            raise ValueError(f"unknown dtype {self.dtype!r}")

    @property
    def bytes(self) -> int:
        n = _DTYPE_BYTES[self.dtype]
        for dim in self.shape:
            n *= int(dim)
        return n


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Static description of one pallas_call launch."""

    kernel: str                              # registry name, e.g. "gram"
    grid: tuple[int, ...]
    blocks: tuple[Block, ...]
    #: (axis label, axis size, tile size) triples to divisibility-check;
    #: axes the kernel pads internally set tile size after padding.
    tiled_axes: tuple[tuple[str, int, int], ...] = ()
    notes: str = ""

    def footprint(self) -> int:
        return sum(b.bytes for b in self.blocks)


def _fmt_bytes(n: float) -> str:
    if n >= 2 ** 20:
        return f"{n / 2 ** 20:.2f} MiB"
    if n >= 2 ** 10:
        return f"{n / 2 ** 10:.1f} KiB"
    return f"{int(n)} B"


def sizing_report(plan: KernelPlan, backend: str = "tpu",
                  budget: int | None = None) -> str:
    """Human-readable per-block VMEM table for ``plan``."""
    budget = vmem_budget(backend) if budget is None else budget
    rows = sorted(plan.blocks, key=lambda b: -b.bytes)
    w = max((len(b.name) for b in rows), default=4)
    lines = [f"kernel {plan.kernel!r}  grid={plan.grid}"]
    for b in rows:
        shape = "x".join(str(d) for d in b.shape)
        lines.append(f"  {b.name:<{w}}  {b.kind:<8}  {shape:>16} "
                     f"{b.dtype:<8} {_fmt_bytes(b.bytes):>12}")
    total = plan.footprint()
    pct = 100.0 * total / budget if budget else float("inf")
    lines.append(f"  {'TOTAL':<{w}}  single-copy footprint "
                 f"{_fmt_bytes(total):>12}  "
                 f"({pct:.0f}% of {_fmt_bytes(budget)} budget, "
                 f"{backend} VMEM {_fmt_bytes(VMEM_BYTES[backend])}/2)")
    if plan.notes:
        lines.append(f"  note: {plan.notes}")
    return "\n".join(lines)


#: the TPU lowering's (sublane, lane) alignment for the last two block dims
_TILE = (8, 128)


def tiling_problems(plan: KernelPlan) -> list[str]:
    """Blocks whose last two dims are neither (8, 128)-aligned nor equal
    to the array's — the rule the Pallas TPU lowering enforces."""
    out = []
    for b in plan.blocks:
        if b.array is None:
            continue
        if len(b.array) != len(b.shape):
            raise ValueError(f"block {b.name!r}: shape {b.shape} and array "
                             f"{b.array} differ in rank")
        for blk, arr, mult in zip(b.shape[-2:], b.array[-2:],
                                  _TILE[-len(b.shape[-2:]):]):
            if blk % mult and blk != arr:
                out.append(
                    f"block {b.name!r} {b.shape} on array {b.array}: dim "
                    f"{blk} is neither a multiple of {mult} nor the "
                    f"array's {arr} (TPU block-shape rule)")
                break
    return out


def check_plan(plan: KernelPlan, backend: str = "tpu",
               budget: int | None = None) -> str:
    """Validate ``plan``; returns the sizing report on success, raises
    :class:`PallasBudgetError` (report included) on failure."""
    budget = vmem_budget(backend) if budget is None else budget
    problems = tiling_problems(plan)
    for axis, size, tile in plan.tiled_axes:
        if tile <= 0:
            problems.append(f"axis {axis}: nonpositive tile {tile}")
        elif size % tile:
            problems.append(
                f"axis {axis}: size {size} not divisible by tile {tile} "
                f"(kernel assumes exact tiling — pad the operand or "
                f"shrink the tile)")
    total = plan.footprint()
    if total > budget:
        problems.append(
            f"single-copy footprint {_fmt_bytes(total)} exceeds the "
            f"{_fmt_bytes(budget)} budget by "
            f"{_fmt_bytes(total - budget)}")
    report = sizing_report(plan, backend, budget)
    if problems:
        detail = "\n".join(f"  - {p}" for p in problems)
        raise PallasBudgetError(
            f"kernel {plan.kernel!r} fails static VMEM/tiling check:\n"
            f"{detail}\n{report}")
    return report


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# ---------------------------------------------------------------------------
# plan builders — each mirrors the BlockSpecs of the real kernel
# ---------------------------------------------------------------------------

def gram_plan(M: int = 4096, N: int = 4096, D: int = 1024, *,
              kind: str = "rbf", bm: int = 256, bn: int = 256,
              bd: int = 512) -> KernelPlan:
    """Mirror of ``kernels.gram._gram_kernel``: out + f32 scratch are
    resident per (i, j) tile while the D axis streams."""
    from repro.kernels import gram as _g
    Mp, Np, Dp = _ceil_to(M, bm), _ceil_to(N, bn), _ceil_to(D, bd)
    bd = min(bd, Dp)
    blocks = [
        Block("xx", (1, bm), array=(1, Mp)),
        Block("zz", (1, bn), array=(1, Np)),
        Block("yx", (1, bm), array=(1, Mp)),
        Block("yz", (1, bn), array=(1, Np)),
        Block("x", (bm, bd), array=(Mp, Dp)),
        Block("z", (bn, bd), array=(Np, Dp)),
        Block("out", (bm, bn), kind="resident", array=(Mp, Np)),
        Block("acc", (bm, bn), kind="scratch"),
    ]
    notes = ""
    if kind in _g.L1_KERNELS:
        # |x-z| has no dot shortcut; the kernel transposes the z slab and
        # sums per-feature (bm, bn) difference slabs chunk by chunk
        blocks += [Block("z_t", (bd, bn), kind="scratch"),
                   Block("l1_slab", (bm, bn), kind="scratch"),
                   Block("l1_part", (bm, bn), kind="scratch")]
        notes = ("laplacian path holds a transposed z slab and two "
                 "(bm, bn) difference slabs")
    return KernelPlan(
        kernel="gram", grid=(Mp // bm, Np // bn, Dp // bd),
        blocks=tuple(blocks),
        tiled_axes=(("M", Mp, bm), ("N", Np, bn), ("D", Dp, bd)),
        notes=notes)


def gram_matvec_plan(K: int = 2, M: int = 4096, N: int = 4096,
                     D: int = 1024, *, bm: int = 256, bn: int = 256,
                     bd: int = 512) -> KernelPlan:
    """Mirror of ``kernels.gram._gram_matvec_kernel``: matrix-free
    K(X,Z)g — the (bm, bn) Gram tile only ever exists in scratch."""
    Mp, Np, Dp = _ceil_to(M, bm), _ceil_to(N, bn), _ceil_to(D, bd)
    bd = min(bd, Dp)
    return KernelPlan(
        kernel="gram_matvec", grid=(K, Mp // bm, Np // bn, Dp // bd),
        blocks=(
            Block("xx", (1, 1, bm), array=(K, 1, Mp)),
            Block("zz", (1, 1, bn), array=(K, 1, Np)),
            Block("g", (1, 1, bn), array=(K, 1, Np)),
            Block("x", (1, bm, bd), array=(K, Mp, Dp)),
            Block("z", (1, bn, bd), array=(K, Np, Dp)),
            Block("out", (1, bm, 1), kind="resident", array=(K, Mp, 1)),
            Block("acc", (bm, bn), kind="scratch"),
            Block("u", (bm, 1), kind="scratch"),
        ),
        tiled_axes=(("M", Mp, bm), ("N", Np, bn), ("D", Dp, bd)))


def fused_cd_plan(K: int = 8, m: int = 4096, B: int = 256, *,
                  source: str = "kernel", d: int = 1024,
                  bd: int = 512) -> KernelPlan:
    """Mirror of ``kernels.dual_cd_block.fused_cd_pass``: ONE launch per
    sweep; per-tile vectors ride as (2, B) / (1, B) slabs and ``u_d`` as a
    partition-resident (nblk, B) block — 4·m bytes, THE documented ceiling
    of the fused layout (near m = 2·10⁶ at the default budget)."""
    if source not in ("kernel", "dense"):
        raise ValueError(f"source must be 'kernel' or 'dense': {source!r}")
    nblk = _ceil_to(m, B) // B
    mp = nblk * B
    blocks = [
        Block("qb", (1, 1, B, B), array=(K, nblk, B, B)),
        Block("a", (1, 1, 2, B), array=(K, nblk, 2, B)),
        Block("u", (1, 1, 1, B), array=(K, nblk, 1, B)),
        Block("v", (1, 1, 1, B), array=(K, nblk, 1, B)),
        Block("a_out", (1, 1, 2, B), array=(K, nblk, 2, B)),
        Block("u_d", (1, nblk, B), kind="resident", array=(K, nblk, B)),
        Block("d", (B, 1), kind="scratch"),
    ]
    if source == "dense":
        blocks.append(Block("Q", (1, B, B), array=(K, mp, mp)))
        grid = (K, nblk, nblk)
    else:
        Dp = _ceil_to(d, bd)
        bd = min(bd, Dp)
        blocks += [
            Block("y_i", (1, 1, B), array=(K, 1, mp)),
            Block("y_j", (1, 1, B), array=(K, 1, mp)),
            Block("xx_j", (1, 1, B), array=(K, 1, mp)),
            Block("xx_i", (1, 1, B), array=(K, 1, mp)),
            Block("x_j", (1, B, bd), array=(K, mp, Dp)),
            Block("x_i", (1, B, bd), array=(K, mp, Dp)),
            Block("acc", (B, B), kind="scratch"),
        ]
        grid = (K, nblk, nblk, Dp // bd)
    return KernelPlan(
        kernel="fused_cd", grid=grid, blocks=tuple(blocks),
        tiled_axes=(("m", mp, B),),
        notes=f"(nblk, B) u_d block is partition-resident: 4*m bytes fp32 "
              f"({_fmt_bytes(4 * mp)} here) — the fused layout's ceiling")


def score_plan(T: int = 1024, S: int = 4096, D: int = 1024, *,
               bt: int = 128, bs: int = 256,
               bd: int = 512) -> KernelPlan:
    """Mirror of ``kernels.score.score_tiles``: serving-side matrix-free
    sum_j c_j k(t, z_j) with the (bt, bs) tile living only in scratch."""
    Tp, Sp, Dp = _ceil_to(T, bt), _ceil_to(S, bs), _ceil_to(D, bd)
    bd = min(bd, Dp)
    return KernelPlan(
        kernel="score", grid=(Tp // bt, Sp // bs, Dp // bd),
        blocks=(
            Block("xx", (1, bt), array=(1, Tp)),
            Block("zz", (1, bs), array=(1, Sp)),
            Block("c", (1, bs), array=(1, Sp)),
            Block("x", (bt, bd), array=(Tp, Dp)),
            Block("z", (bs, bd), array=(Sp, Dp)),
            Block("out", (bt, 1), kind="resident", array=(Tp, 1)),
            Block("acc", (bt, bs), kind="scratch"),
            Block("u", (bt, 1), kind="scratch"),
        ),
        tiled_axes=(("T", Tp, bt), ("S", Sp, bs), ("D", Dp, bd)))


def odm_grad_plan(M: int = 65536, d: int = 2048, *,
                  bm: int = 512) -> KernelPlan:
    """Mirror of ``kernels.odm_grad._odm_grad_kernel``: full-width w and
    out slabs resident while the batch streams in bm rows."""
    Mp = _ceil_to(M, bm)
    return KernelPlan(
        kernel="odm_grad", grid=(Mp // bm,),
        blocks=(
            Block("w", (1, d), kind="resident", array=(1, d)),
            Block("x", (bm, d), array=(Mp, d)),
            Block("y", (1, 1, bm), array=(Mp // bm, 1, bm)),
            Block("out", (1, d), kind="resident", array=(1, d)),
        ),
        tiled_axes=(("M", Mp, bm),),
        notes="w/out are full-width residents; ops._shrink_bm halves bm "
              "when the (bm, d) stream slab crosses 8 MiB")


def svrg_grad_plan(B: int = 4096, d: int = 2048, *,
                   bm: int = 512) -> KernelPlan:
    """Mirror of ``kernels.odm_grad._svrg_grad_kernel``: the DSVRG inner
    step — (w, w_anchor) pair + anchor full gradient resident."""
    Bp = _ceil_to(B, bm)
    return KernelPlan(
        kernel="odm_svrg_grad", grid=(Bp // bm,),
        blocks=(
            Block("wa", (2, d), kind="resident", array=(2, d)),
            Block("h", (1, d), kind="resident", array=(1, d)),
            Block("inv", (1, 1), kind="resident", array=(1, 1)),
            Block("x", (bm, d), array=(Bp, d)),
            Block("y", (1, 1, bm), array=(Bp // bm, 1, bm)),
            Block("wt", (1, 1, bm), array=(Bp // bm, 1, bm)),
            Block("out", (1, d), kind="resident", array=(1, d)),
        ),
        tiled_axes=(("B", Bp, bm),))


#: kernel registry name -> default plan builder (kwargs mirror the real
#: entry points' tiling knobs)
PLAN_BUILDERS: dict[str, Callable[..., KernelPlan]] = {
    "gram": gram_plan,
    "gram_matvec": gram_matvec_plan,
    "fused_cd": fused_cd_plan,
    "score": score_plan,
    "odm_grad": odm_grad_plan,
    "odm_svrg_grad": svrg_grad_plan,
}


def default_plans() -> dict[str, KernelPlan]:
    """One representative plan per registered kernel, at each kernel's
    default tiling and production-representative operand sizes."""
    return {name: build() for name, build in PLAN_BUILDERS.items()}


def check_kernels(backend: str = "tpu") -> dict[str, str]:
    """Check every registered kernel's default plan; returns the sizing
    reports, raises :class:`PallasBudgetError` on the first failure."""
    return {name: check_plan(plan, backend)
            for name, plan in default_plans().items()}
