"""Declarative jaxpr lint: walk a traced program, check plan invariants.

``jax.make_jaxpr`` gives the full program plan — every primitive, every
sub-jaxpr (scan/while bodies, cond branches, pjit calls, pallas kernel
bodies) — before anything compiles or runs. This module walks that tree
into a flat list of :class:`Site`\\ s (primitive + structural path) and
runs declarative :class:`Rule`\\ s over it, so the bug classes this repo
has actually hit become machine-checked assertions:

* **launch budgets** — :func:`max_pallas_calls` pins how many
  ``pallas_call``\\ s a traced function may contain (the fused-CD-pass
  "one launch per pass" pin, the serving "one launch per batch" pin).
* **gather-free paths** — :func:`gather_free` asserts a hot path contains
  no ``gather`` (the served score path must never re-apply the partition
  permutation per call).
* **collectives inside loops** — :func:`no_collectives_in_loops` detects
  the PR 3 hoisting trap statically: XLA will NOT hoist a loop-invariant
  collective out of a ``while``/``scan`` body, so an all-gather of an
  invariant slab inside an epoch loop multiplies its wire bytes by the
  trip count. Legitimately per-iteration collectives (e.g. the sharded
  DSVRG loss psum) are allow-listed by name.
* **host sync inside loops** — :func:`no_host_sync_in_loops` keeps
  callbacks/infeed out of hot loop bodies (each one is a device→host
  round trip per iteration).
* **scan-length assertions** — :func:`expect_scan` pins trace-once scan
  drivers (e.g. "all epochs live in ONE scan of length ``epochs``").

Entry points: :func:`trace` a zero-arg thunk to a jaxpr, :func:`lint`
to collect violations, :func:`check` to raise :class:`InvariantViolation`
with a formatted report, :func:`count_primitive` for count pins.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Sequence

import jax

__all__ = [
    "InvariantViolation", "Site", "Rule", "Violation", "trace",
    "iter_sites", "lint", "check", "count_primitive", "scan_lengths",
    "max_primitive", "max_pallas_calls", "forbid_primitive", "gather_free",
    "no_collectives_in_loops", "no_host_sync_in_loops", "expect_scan",
    "COLLECTIVE_PRIMS", "HOST_SYNC_PRIMS", "GATHER_PRIMS", "LOOP_FRAMES",
]


class InvariantViolation(AssertionError):
    """A declared plan-level invariant does not hold."""


#: structural frames that mean "inside a loop body" (trip count > 1 —
#: a while condition re-executes per trip, so it counts as loop context)
LOOP_FRAMES = frozenset({"scan_body", "while_body", "while_cond"})

#: cross-device communication primitives (jax names them stably)
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "reduce_scatter",
})

#: primitives that force a device <-> host round trip
HOST_SYNC_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
})

#: dynamic-indexing primitives a "gather-free" hot path must not contain
GATHER_PRIMS = frozenset({"gather"})


@dataclasses.dataclass(frozen=True)
class Site:
    """One primitive occurrence inside a (possibly nested) jaxpr."""

    prim: str
    path: tuple[str, ...]                  # enclosing frames, outermost first
    eqn: object = dataclasses.field(compare=False, hash=False, default=None)

    @property
    def loop_depth(self) -> int:
        return sum(1 for f in self.path if f in LOOP_FRAMES)

    @property
    def where(self) -> str:
        return "/".join(self.path) if self.path else "<top>"

    def __str__(self) -> str:
        return f"{self.where}:{self.prim}"


def _as_jaxprs(val) -> Iterator:
    """Yield every Jaxpr inside a params value (ClosedJaxpr, Jaxpr, or a
    tuple/list of either — jax's own containers for sub-programs)."""
    if hasattr(val, "eqns"):                           # Jaxpr
        yield val
    elif hasattr(val, "jaxpr") and hasattr(val, "consts"):   # ClosedJaxpr
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _as_jaxprs(item)


def _frame_label(prim: str, key: str) -> str:
    if prim == "scan" and key == "jaxpr":
        return "scan_body"
    if prim == "while" and key == "body_jaxpr":
        return "while_body"
    if prim == "while" and key == "cond_jaxpr":
        return "while_cond"
    return prim                                        # pjit, cond, pallas...


def iter_sites(jaxpr, path: tuple[str, ...] = ()) -> Iterator[Site]:
    """Every primitive occurrence in ``jaxpr``, depth-first, sub-jaxprs
    included. ``jaxpr`` may be a Jaxpr or ClosedJaxpr."""
    for inner in _as_jaxprs(jaxpr):
        for eqn in inner.eqns:
            name = eqn.primitive.name
            yield Site(prim=name, path=path, eqn=eqn)
            for key, val in eqn.params.items():
                for sub in _as_jaxprs(val):
                    yield from iter_sites(sub,
                                          path + (_frame_label(name, key),))


def trace(fn: Callable[[], object]):
    """Trace a zero-arg thunk (closing over its inputs) to a ClosedJaxpr.
    Nothing executes and nothing compiles — this is the plan, pre-device."""
    return jax.make_jaxpr(fn)()


def _sites_of(target) -> list[Site]:
    if callable(target):
        target = trace(target)
    return list(iter_sites(target))


def count_primitive(fn: Callable[[], object], prim: str) -> int:
    """Occurrences of ``prim`` in the traced plan of ``fn`` (jitted
    constituents included — their sub-jaxprs are walked, so no trace-cache
    clearing is needed, unlike the old monkeypatch counter)."""
    return sum(1 for s in _sites_of(fn) if s.prim == prim)


def scan_lengths(target) -> list[int]:
    """``length`` of every scan in the plan, outermost-first."""
    return [int(s.eqn.params["length"]) for s in _sites_of(target)
            if s.prim == "scan"]


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    message: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.message}"


@dataclasses.dataclass(frozen=True)
class Rule:
    """A declarative invariant over the flattened site list."""

    name: str
    description: str
    check: Callable[[list[Site]], list[str]] = dataclasses.field(
        compare=False)

    def run(self, sites: list[Site]) -> list[Violation]:
        return [Violation(rule=self.name, message=m)
                for m in self.check(sites)]


def max_primitive(prim: str, n: int, *, name: str | None = None) -> Rule:
    """At most ``n`` occurrences of ``prim`` anywhere in the plan."""

    def chk(sites: list[Site]) -> list[str]:
        hits = [s for s in sites if s.prim == prim]
        if len(hits) > n:
            at = ", ".join(str(s) for s in hits)
            return [f"{len(hits)} x {prim} in the plan, budget is {n} "
                    f"(at: {at})"]
        return []

    return Rule(name=name or f"max_{prim}_{n}",
                description=f"at most {n} {prim} in the traced plan",
                check=chk)


def max_pallas_calls(n: int) -> Rule:
    """Kernel-launch budget: at most ``n`` ``pallas_call``\\ s."""
    return max_primitive("pallas_call", n, name=f"max_pallas_calls_{n}")


def forbid_primitive(prims: Sequence[str] | frozenset, *, name: str,
                     reason: str = "") -> Rule:
    """No occurrence of any of ``prims`` anywhere in the plan."""
    pset = frozenset(prims)

    def chk(sites: list[Site]) -> list[str]:
        why = f" — {reason}" if reason else ""
        return [f"forbidden primitive {s}{why}"
                for s in sites if s.prim in pset]

    return Rule(name=name, description=f"forbids {sorted(pset)}", check=chk)


def gather_free() -> Rule:
    """The plan contains no gather: hot score paths must never re-gather
    (the partition permutation is applied once at model-compile time)."""
    return forbid_primitive(
        GATHER_PRIMS, name="gather_free",
        reason="this path is pinned gather-free (permutations are applied "
               "once at compile_model time, never per call)")


def _in_loop_rule(pset: frozenset, *, name: str, reason: str,
                  allow: Sequence[str] = ()) -> Rule:
    allowed = frozenset(allow)

    def chk(sites: list[Site]) -> list[str]:
        return [f"{s} inside a loop body — {reason}"
                for s in sites
                if s.prim in pset and s.prim not in allowed
                and s.loop_depth > 0]

    return Rule(name=name,
                description=f"forbids {sorted(pset - allowed)} inside "
                            f"while/scan bodies", check=chk)


def no_collectives_in_loops(allow: Sequence[str] = ()) -> Rule:
    """No collective inside a ``while``/``scan`` body (the PR 3 hoisting
    trap: XLA does not hoist loop-invariant collectives, so a gather of an
    invariant slab pays its wire bytes once per trip). ``allow`` names
    collectives that are legitimately per-iteration (e.g. ``psum`` of a
    per-epoch loss)."""
    return _in_loop_rule(
        COLLECTIVE_PRIMS, name="no_collectives_in_loops", allow=allow,
        reason="XLA will not hoist it out; hoist loop-invariant "
               "collectives above the loop yourself (PR 3 trap)")


def no_host_sync_in_loops() -> Rule:
    """No host callback/infeed inside a loop body: one device-host round
    trip per iteration serializes the hot loop."""
    return _in_loop_rule(
        HOST_SYNC_PRIMS, name="no_host_sync_in_loops",
        reason="each iteration would synchronize with the host")


def expect_scan(length: int, count: int = 1, *,
                name: str | None = None) -> Rule:
    """Exactly ``count`` scans of trip count ``length`` in the plan — the
    trace-once driver shape ("all epochs in ONE lax.scan")."""

    def chk(sites: list[Site]) -> list[str]:
        lens = [int(s.eqn.params["length"]) for s in sites
                if s.prim == "scan"]
        got = sum(1 for ln in lens if ln == length)
        if got != count:
            return [f"expected {count} scan(s) of length {length}, found "
                    f"{got} (all scan lengths: {lens})"]
        return []

    return Rule(name=name or f"expect_scan_{length}x{count}",
                description=f"{count} scan(s) of length {length}",
                check=chk)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint(target, rules: Sequence[Rule]) -> list[Violation]:
    """Run ``rules`` over the plan of ``target`` (a zero-arg thunk, a
    Jaxpr, or a ClosedJaxpr); returns all violations."""
    sites = _sites_of(target)
    out: list[Violation] = []
    for rule in rules:
        out.extend(rule.run(sites))
    return out


def check(target, rules: Sequence[Rule], *, subject: str = "plan") -> None:
    """:func:`lint` and raise :class:`InvariantViolation` on violations."""
    violations = lint(target, rules)
    if violations:
        lines = "\n".join(f"  {v}" for v in violations)
        raise InvariantViolation(
            f"{subject}: {len(violations)} jaxpr invariant violation(s):\n"
            f"{lines}")
