#!/usr/bin/env python3
"""The precision control: a cell run with the program's matrix products
one precision step below what the configuration states.

    python3 bench/control.py --workload <name> --seed <n> --seconds <s> \
        [--precision HIGH|DEFAULT] [--only fused_cd_pass]

Every configuration states float32 with every matrix product at
``Precision.HIGHEST`` (``repro.precision.MATMUL``). The control lowers that
one setting to ``HIGH`` (three bf16 passes), the step a later change
would be tempted to take for speed, or with ``--precision DEFAULT`` to one
bf16 pass, and runs the cell's harness as ``bench/run.py`` does. With
``--only fused_cd_pass`` it lowers the fused CD kernel's products alone
(the step a later change to that kernel would take) and keeps every other
product, the scorer's included, at ``HIGHEST``. Its ``correct`` has to
come out false (PERF.md gives, per cell, what each control reads). The
benchmark's own runs never run it.

Two lowerings lack ``HIGH``: the Pallas TPU kernels (Mosaic accepts only
DEFAULT and HIGHEST) and the CPU (float32 whatever the precision). There
:func:`lower_precision` lowers ``HIGH`` products as the TPU's matrix unit
computes them (operands split into a bf16 high and low part, three
products, the low-by-low one dropped), so the control runs on the chip and
the tests run it at a small size.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import sys                     # noqa: E402
from pathlib import Path       # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _bf16_passes(jax, passes, dimension_numbers, preferred_element_type):
    """A float32 product as the TPU's matrix unit computes it at ``HIGH``
    (3 passes: each operand split into a bf16 high and low part, the
    low-by-low product dropped) or ``DEFAULT`` (1 pass: both operands
    rounded to bf16). Products of bf16 values are exact in float32 and
    accumulate in float32."""
    import jax.numpy as jnp

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def dot(u, v):
        return jax.lax.dot_general(
            u, v, dimension_numbers, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=preferred_element_type)

    def f(a, b):
        ah, bh = bf16(a), bf16(b)
        if passes == 1:
            return dot(ah, bh)
        return dot(ah, bh) + dot(ah, bf16(b - bh)) + dot(bf16(a - ah), bh)
    return f


def _lowered(jax, precision):
    """Passes of the emulated precision, or None for a native one."""
    p = () if precision is None else tuple(precision)
    if jax.lax.Precision.HIGH in p:
        return 3
    if jax.lax.Precision.DEFAULT in p and _TARGET[0] == "DEFAULT":
        return 1
    return None


_TARGET = ["HIGH"]


def _emulate(jax) -> None:
    """Lower the control's products where no native lowering has their
    precision: ``HIGH`` in Pallas TPU kernels (Mosaic accepts only DEFAULT
    and HIGHEST), and both on the CPU (float32 whatever the precision)."""
    from jax._src.interpreters import mlir
    from jax._src.lax import lax as lax_internal
    from jax._src.pallas.mosaic import lowering as mosaic

    prim = lax_internal.dot_general_p
    generic = lax_internal._dot_general_lower

    def xla_rule(ctx, lhs, rhs, *, dimension_numbers, precision,
                 preferred_element_type, **kw):
        passes = _lowered(jax, precision)
        if passes is None:
            return generic(ctx, lhs, rhs,
                           dimension_numbers=dimension_numbers,
                           precision=precision,
                           preferred_element_type=preferred_element_type,
                           **kw)
        f = _bf16_passes(jax, passes, dimension_numbers,
                         preferred_element_type)
        return mlir.lower_fun(f, multiple_results=False)(ctx, lhs, rhs)

    if jax.default_backend() == "cpu":
        mlir.register_lowering(prim, xla_rule, platform="cpu")

    kernel_rules = mosaic.lowering_rules[mosaic.tpu_core.KernelType.TC]
    native = kernel_rules[prim]

    def kernel_rule(ctx, x, y, *, dimension_numbers, precision,
                    preferred_element_type, **kw):
        if _lowered(jax, precision) != 3:       # Mosaic has DEFAULT itself
            return native(ctx, x, y, dimension_numbers=dimension_numbers,
                          precision=precision,
                          preferred_element_type=preferred_element_type,
                          **kw)
        f = _bf16_passes(jax, 3, dimension_numbers, preferred_element_type)
        return mosaic.lower_fun(f, multiple_results=False)(ctx, x, y)

    kernel_rules[prim] = kernel_rule


def lower_precision(to: str = "HIGH") -> None:
    """Set ``MATMUL`` to ``to`` (``HIGH``, or ``DEFAULT``: one bf16 pass) in
    every loaded ``repro`` module that took it; call before anything is
    traced."""
    import importlib
    import pkgutil

    import jax
    import repro
    for info in pkgutil.walk_packages(repro.__path__, "repro."):
        if info.name.split(".")[1] in ("kernels", "core", "serve", "api",
                                       "data", "precision"):
            importlib.import_module(info.name)
    HIGHEST = jax.lax.Precision.HIGHEST
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro") and getattr(mod, "MATMUL", None) \
                is HIGHEST:
            mod.MATMUL = getattr(jax.lax.Precision, to)
    _TARGET[0] = to
    _emulate(jax)


def lower_fused_cd_pass(to: str = "HIGH") -> None:
    """Lower the products of ``dual_cd_block.fused_cd_pass`` alone: its
    own and those of the Gram tiles it rebuilds (``repro.kernels.gram``,
    read while the kernel is traced); every other product stays at
    ``HIGHEST``. Call before anything is traced."""
    import jax
    from repro.kernels import dual_cd_block, gram
    real = dual_cd_block.fused_cd_pass
    low = getattr(jax.lax.Precision, to)

    def lowered(*args, **kw):
        kept = dual_cd_block.MATMUL, gram.MATMUL
        dual_cd_block.MATMUL = gram.MATMUL = low
        try:
            return real(*args, **kw)
        finally:
            dual_cd_block.MATMUL, gram.MATMUL = kept
    dual_cd_block.fused_cd_pass = lowered
    _TARGET[0] = to
    _emulate(jax)


def _option(argv: list, name: str, default):
    if name not in argv:
        return default
    i = argv.index(name)
    value = argv[i + 1]
    del argv[i:i + 2]
    return value


def main() -> int:
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness import runner
    runner.cache_env(ROOT)
    argv = sys.argv[1:]
    to = _option(argv, "--precision", "HIGH")
    only = _option(argv, "--only", None)
    if only == "fused_cd_pass":
        lower_fused_cd_pass(to)
    elif only is None:
        lower_precision(to)
    else:
        sys.exit(f"control: --only takes fused_cd_pass, not {only!r}")
    return runner.main(argv, t_start=_T0)


if __name__ == "__main__":
    sys.exit(main())
