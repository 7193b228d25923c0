"""repro.analysis — static analysis of the compiled-program plan.

The repo's correctness story at scale is plan-level, not math-level: the
bug classes that actually bit us (PR 3's silent-wrong-answer trio, the
collectives-stuck-inside-``while`` hoisting trap, the Mosaic VMEM ceiling
on the fused pass's partition-resident u_d block) are all visible in the
traced program or the kernel launch plan *before a device ever runs*.
This package turns those checks into a subsystem:

* :mod:`repro.analysis.jaxpr_lint`   — jaxpr walker + declarative rule
  engine over traced functions (launch budgets, gather-free paths,
  collectives/host-sync inside loop bodies, scan-length assertions).
* :mod:`repro.analysis.pallas_check` — static per-tile VMEM footprint and
  tile-divisibility checks for every registered Pallas kernel, against a
  per-backend budget, with a sizing report on failure.
* :mod:`repro.analysis.invariants`   — the registry where kernels and
  training routes DECLARE their invariants (launch counts, VMEM plans,
  trace/gather counters, collective ceilings); one uniform battery in
  ``tests/test_analysis.py`` verifies every declaration.
* :mod:`repro.analysis.boundary_lint` — AST lint of repo conventions
  (no hardcoded tile/step knobs, pallas_call containment), run by
  ``scripts/lint.py`` and CI.

``boundary_lint`` is stdlib-only so ``scripts/lint.py`` stays fast; the
other modules import jax and are loaded lazily here.
"""
from __future__ import annotations

_SUBMODULES = ("jaxpr_lint", "pallas_check", "invariants", "boundary_lint")


def __getattr__(name: str):
    if name in _SUBMODULES:
        import importlib
        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
