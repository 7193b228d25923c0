"""The invariant registry: kernels and routes DECLARE, one battery checks.

Before this module the repo's plan-level pins lived wherever they were
first needed — ``sodm.perm_gather_count`` in core, launch-count asserts
in ``benchmarks/kernels_bench.py``, the trace-once pin in
``tests/test_dsvrg.py``. Each was real, none was discoverable, and a new
kernel or route shipped with whatever pins its author remembered to add.
Here every Pallas kernel and every registered training route declares
its invariants as data:

    declare(Invariant(
        name="kernels.score.single_launch", subject="score",
        kind="kernel", description="one pallas_call per request batch",
        verify=_score_single_launch))

``tests/test_analysis.py`` runs ONE parametrized battery over
:func:`invariants`, and a meta-test asserts every kernel in
``pallas_check.PLAN_BUILDERS`` and every route in
``api.registry.routes()`` has at least one declaration — forgetting the
pin is itself a test failure.

Also hosts the process-wide :class:`Counter` store backing the legacy
regression pins (``sodm.perm_gather_count``, ``dsvrg.epoch_trace_count``
are thin aliases over these), and :func:`count_pallas_calls`, the
jaxpr-walk launch counter (no monkeypatching, no trace-cache clearing).

Import discipline: this module imports only :mod:`repro.analysis` and
jax at the top level; every verify closure lazy-imports the subsystem it
checks, so ``repro.core`` modules can import this one for counters
without a cycle.
"""
from __future__ import annotations

# lint: allow[T001] — the verify closures trace kernels at minimal probe
# shapes; their tiny tile kwargs are the fixture, not production config.

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_lint as jl
from repro.analysis import pallas_check as pc

__all__ = [
    "Counter", "counter", "counters", "Invariant", "declare", "invariants",
    "get", "verify", "verify_all", "count_pallas_calls", "COMPONENTS",
]


# ---------------------------------------------------------------------------
# counters (process-wide regression pins)
# ---------------------------------------------------------------------------

class Counter:
    """A named append-only event counter. ``events`` is a plain list so
    legacy module globals can alias it in place (``dsvrg._TRACE_EVENTS``
    IS ``counter("dsvrg.epoch_trace").events`` — same object)."""

    def __init__(self, name: str):
        self.name = name
        self.events: list = []

    @property
    def count(self) -> int:
        return len(self.events)

    def bump(self, event=None) -> None:
        self.events.append(event)

    def reset(self) -> None:
        del self.events[:]

    def __repr__(self) -> str:
        return f"Counter({self.name!r}, count={self.count})"


_COUNTERS: dict[str, Counter] = {}


def counter(name: str) -> Counter:
    """Get-or-create the process-wide counter ``name``."""
    got = _COUNTERS.get(name)
    if got is None:
        got = _COUNTERS[name] = Counter(name)
    return got


def counters() -> dict[str, Counter]:
    return dict(_COUNTERS)


def count_pallas_calls(fn) -> int:
    """Count ``pallas_call`` sites in the traced plan of the zero-arg
    thunk ``fn`` — by walking the jaxpr (sub-jaxprs of jitted
    constituents included), so unlike the old monkeypatch counter it
    needs no ``clear_cache()`` discipline and cannot undercount on a
    warm trace cache."""
    return jl.count_primitive(fn, "pallas_call")


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Invariant:
    """One declared plan-level invariant.

    ``subject`` is the kernel registry name (a ``pallas_check.
    PLAN_BUILDERS`` key) or the route name (an ``api.registry`` route);
    ``kind`` says which namespace that is. ``verify`` is a zero-arg
    callable that raises ``AssertionError`` (usually
    :class:`~repro.analysis.jaxpr_lint.InvariantViolation`) on failure;
    its return value, if any, is a human-readable result. ``slow`` marks
    declarations the quick CI tier skips (subprocess compiles etc.)."""

    name: str
    subject: str
    kind: str                      # "kernel" | "route" | "component"
    description: str
    verify: Callable[[], object] = dataclasses.field(compare=False)
    slow: bool = False

    def __post_init__(self):
        if self.kind not in ("kernel", "route", "component"):
            raise ValueError(f"kind must be 'kernel', 'route' or "
                             f"'component', got {self.kind!r}")


#: fault-tolerance / observability components under the PR 6 meta-coverage
#: rule: each must carry >= 1 ``kind="component"`` declaration (asserted by
#: tests/test_analysis.py alongside the kernel and route coverage)
COMPONENTS = ("checkpoint", "data", "faults", "resume", "tracker",
              "observe")

_REGISTRY: dict[str, Invariant] = {}


def declare(inv: Invariant) -> Invariant:
    """Register ``inv``; duplicate names raise (a pin silently replaced
    is a pin silently dropped)."""
    if inv.name in _REGISTRY:
        raise ValueError(f"invariant {inv.name!r} already declared")
    _REGISTRY[inv.name] = inv
    return inv


def invariants() -> tuple[Invariant, ...]:
    """All declared invariants, name-sorted (stable parametrize order)."""
    return tuple(_REGISTRY[k] for k in sorted(_REGISTRY))


def get(name: str) -> Invariant:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no invariant {name!r}; declared: "
                       f"{sorted(_REGISTRY)}") from None


def verify(name: str):
    """Run one invariant by name; raises on violation."""
    return get(name).verify()


def verify_all(include_slow: bool = False) -> dict[str, object]:
    """Run every declared invariant; returns {name: result}. Raises on
    the first violation (the battery in tests runs them individually)."""
    return {inv.name: inv.verify() for inv in invariants()
            if include_slow or not inv.slow}


# ---------------------------------------------------------------------------
# shared tiny fixtures for the built-in declarations
# ---------------------------------------------------------------------------

def _toy_data(M: int = 32, d: int = 4, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.concatenate([jax.random.normal(k1, (M // 2, d)) + 1.0,
                         jax.random.normal(k2, (M // 2, d)) - 1.0])
    y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
    perm = jax.random.permutation(k3, M)
    return x[perm], y[perm]


def _assert_single_launch(thunk, what: str) -> str:
    n = count_pallas_calls(thunk)
    if n != 1:
        raise jl.InvariantViolation(
            f"{what}: expected exactly 1 pallas_call in the plan, "
            f"found {n}")
    return f"{what}: 1 launch"


# ---------------------------------------------------------------------------
# kernel invariants
# ---------------------------------------------------------------------------

def _gram_single_launch():
    from repro.kernels import ops
    x, _ = _toy_data(16, 8)
    z, _ = _toy_data(16, 8, seed=1)
    spec = ops._RbfSpec(0.5)
    return _assert_single_launch(
        lambda: ops.gram(x, z, spec, bm=8, bn=8, bd=8),
        "gram (one launch per Gram block, all D-chunks inside the grid)")


def _gram_vmem():
    out = [pc.check_plan(pc.gram_plan())]
    # the laplacian path carries an extra (bm, bn, chunk) broadcast slab
    out.append(pc.check_plan(pc.gram_plan(kind="laplacian")))
    return "\n".join(out)


def _gram_matvec_single_launch():
    from repro.kernels import ops
    x, y = _toy_data(16, 8)
    xs = x.reshape(2, 8, 8)
    g = jnp.ones((2, 8))
    spec = ops._RbfSpec(0.5)
    return _assert_single_launch(
        lambda: ops.gram_matvec(xs, g, spec, bm=8, bn=8, bd=8),
        "gram_matvec (all K partitions and tiles in one launch)")


def _gram_matvec_vmem():
    return pc.check_plan(pc.gram_matvec_plan())


def _score_single_launch():
    from repro.kernels import score
    x, _ = _toy_data(16, 8)
    z, _ = _toy_data(32, 8, seed=1)
    c = jnp.ones((32,))
    return _assert_single_launch(
        lambda: score.score_tiles(x, z, c, kind="rbf", gamma=0.5, bt=8,
                                  bs=8, bd=8, interpret=True),
        "score (one launch per request batch)")


def _score_gather_free():
    from repro.core import kernel_fns as kf
    from repro.kernels import ops
    x, _ = _toy_data(16, 8)
    z, _ = _toy_data(32, 8, seed=1)
    c = jnp.ones((32,))
    spec = kf.KernelSpec(name="rbf", gamma=0.5)
    rules = [jl.gather_free(), jl.no_host_sync_in_loops()]
    # the kernel path AND the interpret-mode streaming path must both
    # stay gather-free: the permutation is applied at compile_model time
    jl.check(lambda: ops.decision_scores(x, z, c, spec, bt=8, bs=8, bd=8,
                                         tiled=True),
             rules, subject="decision_scores(tiled=True)")
    jl.check(lambda: ops.decision_scores(x, z, c, spec, bt=8),
             rules, subject="decision_scores(auto)")
    return "score paths are gather-free"


def _score_vmem():
    return pc.check_plan(pc.score_plan())


def _odm_grad_single_launch():
    from repro.kernels import ops
    x, y = _toy_data(16, 8)
    w = jnp.zeros(8)
    return _assert_single_launch(
        lambda: ops.odm_grad(w, x, y, bm=8),
        "odm_grad (full primal gradient in one launch)")


def _odm_grad_vmem():
    out = [pc.check_plan(pc.odm_grad_plan())]
    # the _shrink_bm policy must keep wide-feature sweeps inside budget
    from repro.kernels import ops
    for d in (1024, 2048, 4096, 8192):
        bm = ops._shrink_bm(512, 65536, d)
        out.append(pc.check_plan(pc.odm_grad_plan(d=d, bm=bm)))
    return "\n".join(out)


def _svrg_grad_single_launch():
    from repro.kernels import ops
    x, y = _toy_data(16, 8)
    w = jnp.zeros(8)
    return _assert_single_launch(
        lambda: ops.svrg_grad(w, w, w, x, y, bm=8),
        "odm_svrg_grad (one launch per inner step)")


def _svrg_grad_vmem():
    return pc.check_plan(pc.svrg_grad_plan())


def _fused_cd_sources(B: int = 8, K: int = 2, d: int = 8):
    from repro.core import kernel_fns as kf
    from repro.kernels import gram as gram_mod
    m = 2 * B
    x, y = _toy_data(K * m, d)
    xs, ys = x.reshape(K, m, d), y.reshape(K, m)
    spec = kf.KernelSpec(name="rbf", gamma=0.5)
    import jax as _jax
    from repro.kernels import dual_cd_block as cdk
    qb = _jax.vmap(lambda q: cdk.extract_diag_blocks(q, B))(
        _jax.vmap(lambda xk, yk: kf.signed_gram(spec, xk, yk))(xs, ys))
    dense = gram_mod.DenseSource(
        _jax.vmap(lambda xk, yk: kf.signed_gram(spec, xk, yk))(xs, ys))
    mfree = gram_mod.make_kernel_source(spec, xs, ys, bm=B, bn=B,
                                        interpret=True)
    a = jnp.zeros((K, m // B, 2 * B))
    u = jnp.zeros((K, m // B, B))
    v = jnp.ones((K, m // B, B))
    return qb, dense, mfree, a, u, v, m


def _fused_cd_single_launch():
    from repro.kernels import dual_cd_block as cdk
    qb, dense, mfree, a, u, v, m = _fused_cd_sources()
    for label, src in (("dense", dense), ("matrix-free", mfree)):
        _assert_single_launch(
            lambda src=src: cdk.fused_cd_pass(
                qb, src, a, u, v, c=1.0, ups=0.5, theta=0.1,
                mscale=float(m), n_steps=4, exit_tol=0.0, interpret=True),
            f"fused_cd_pass[{label}] (one launch per sweep)")
    return "fused_cd_pass: 1 launch per pass, both sources"


def _fused_cd_vmem():
    return "\n".join([pc.check_plan(pc.fused_cd_plan(source="kernel")),
                      pc.check_plan(pc.fused_cd_plan(source="dense"))])


def _fused_cd_vmem_ceiling():
    plan = pc.fused_cd_plan(m=2_000_000, source="kernel")
    try:
        pc.check_plan(plan)
    except pc.PallasBudgetError as e:
        msg = str(e)
        assert "u_d" in msg and "exceeds" in msg, msg
        return ("m=2*10^6 fused plan correctly rejected at plan time "
                "(partition-resident u_d block)")
    raise jl.InvariantViolation(
        "the m=2*10^6 fused matrix-free plan fit the VMEM budget — the "
        "(nblk, B) u_d ceiling (ROADMAP open item 1) is no longer being "
        "caught; if the kernel layout changed, update fused_cd_plan")


# ---------------------------------------------------------------------------
# route invariants
# ---------------------------------------------------------------------------

_LINEAR_ROUTES = ("dsvrg", "svrg", "csvrg")


def _route_cfg(route: str):
    from repro.core import dsvrg as dsvrg_mod
    from repro.core import sodm as sodm_mod
    dcfg = dsvrg_mod.DSVRGConfig(n_partitions=4, epochs=2, batch=8,
                                 n_landmarks=4)
    return sodm_mod.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-4,
                               max_sweeps=50, dsvrg=dcfg)


def _facade_artifact(route: str):
    """ODMEstimator.fit(route) on a toy problem returns a deployable
    FittedODM without tripping any legacy-shim FutureWarning — the facade
    never routes through its own deprecated entry points."""
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import kernel_fns as kf
    from repro.serve.model import FittedODM
    kernel = "linear" if route in _LINEAR_ROUTES else "rbf"
    problem = ProblemSpec(kernel=kf.KernelSpec(name=kernel, gamma=0.5))
    x, y = _toy_data(32, 4)
    est = ODMEstimator(problem, route=route, cfg=_route_cfg(route))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        model, report = est.fit(x, y, jax.random.PRNGKey(0))
    legacy = [w for w in caught if issubclass(w.category, FutureWarning)]
    if legacy:
        raise jl.InvariantViolation(
            f"route {route!r} fit raised legacy FutureWarning(s): "
            f"{[str(w.message) for w in legacy]}")
    assert isinstance(model, FittedODM), type(model)
    assert report.route == route, report.route
    preds = est.predict(x)
    assert preds.shape == (32,)
    return f"route {route}: FittedODM artifact, no legacy warnings"


def _make_facade_invariant(route: str) -> Callable[[], object]:
    return lambda: _facade_artifact(route)


def _sodm_gather_once():
    """The partition permutation is gathered ONCE per fitted model:
    repeated predicts through the cached compiled model add nothing."""
    from repro.core import kernel_fns as kf
    from repro.core import odm, sodm
    x, y = _toy_data(32, 4)
    spec = kf.KernelSpec(name="rbf", gamma=0.5)
    params = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)
    cfg = _route_cfg("sodm")
    res = sodm._solve(spec, x, y, params, cfg, jax.random.PRNGKey(0))
    c0 = sodm.perm_gather_count()
    sodm.predict(spec, res, x, y, x[:8])
    c1 = sodm.perm_gather_count()
    sodm.predict(spec, res, x, y, x[8:16])
    c2 = sodm.perm_gather_count()
    if not (c1 == c0 + 1 and c2 == c1):
        raise jl.InvariantViolation(
            f"perm gather pin broken: counts {c0} -> {c1} -> {c2}; "
            f"expected exactly one gather at model compile, zero per "
            f"predict")
    return "sodm: 1 perm gather per fitted model, 0 per predict"


def _dsvrg_trace_once():
    """A whole DSVRG solve is ONE jit trace; re-solving the same config
    and shapes re-traces nothing (the scan driver is cache-stable)."""
    from repro.core import dsvrg, odm
    x, y = _toy_data(32, 4)
    params = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)
    cfg = dsvrg.DSVRGConfig(n_partitions=4, epochs=2, batch=8,
                            n_landmarks=4)
    key = jax.random.PRNGKey(0)
    dsvrg._solve(x, y, params, cfg, key)          # warm (may or may not trace)
    n1 = dsvrg.epoch_trace_count()
    dsvrg._solve(x, y, params, cfg, key)
    n2 = dsvrg.epoch_trace_count()
    if n2 != n1:
        raise jl.InvariantViolation(
            f"dsvrg re-traced on an identical config: trace count "
            f"{n1} -> {n2} (cfg or shapes are not cache-stable)")
    return "dsvrg: identical re-solve adds 0 traces"


def _dsvrg_epoch_scan_shape():
    """The local driver's plan: ONE scan of length cfg.epochs (all epochs
    in one trace), and no collective or host-sync primitive anywhere in
    its loop bodies — a single-process solve never talks to the wire."""
    from repro.core import dsvrg, odm
    EPOCHS = 5                       # distinct from K=2 and S=2 below
    x, y = _toy_data(8, 4)
    params = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)
    cfg = dsvrg.DSVRGConfig(n_partitions=2, epochs=EPOCHS, batch=2)
    xs, ys, wts = dsvrg._pad_batches(x.reshape(2, 4, 4),
                                     y.reshape(2, 4), cfg.batch)
    w0 = jnp.zeros(4)
    thunk = lambda: dsvrg._run(w0, xs, ys, wts, params=params, cfg=cfg,
                               M=8)
    jl.check(thunk,
             [jl.expect_scan(EPOCHS, count=1, name="one_epoch_scan"),
              jl.no_collectives_in_loops(),
              jl.no_host_sync_in_loops()],
             subject="dsvrg._run")
    return f"dsvrg._run: one scan of length {EPOCHS}, loop bodies clean"


_SHARDED_HOIST_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import dsvrg
from repro.core.odm import ODMParams
from repro.launch import hlo_analysis as ha

mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
params = ODMParams(lam=1.0, theta=0.1, ups=0.5)
M, d, K, batch = 32, 4, 2, 8


def all_gathers(epochs):
    cfg = dsvrg.DSVRGConfig(n_partitions=K, epochs=epochs, batch=batch,
                            schedule="serial")
    xs = jnp.zeros((K, M // K, d))
    ys = jnp.ones((K, M // K))
    xsb, ysb, wts = dsvrg._pad_batches(xs, ys, batch)
    run = dsvrg._make_sharded_run(mesh, params, cfg, M, "data")
    hlo = run.lower(jnp.zeros(d), xsb, ysb, wts).compile().as_text()
    return ha.collective_bytes(hlo).count_by_kind.get("all-gather", 0)


a2, a6 = all_gathers(2), all_gathers(6)
assert a2 > 0, "no all-gather found at all — serial schedule changed?"
assert a2 == a6, (
    f"all-gather count grows with the epoch count ({a2} at 2 epochs vs "
    f"{a6} at 6): the serial-schedule slab gather has slid back inside "
    f"the epoch scan (the PR 3 hoisting trap)")
print(f"OK all_gathers={a2} at both epoch counts")
"""


def _dsvrg_sharded_gather_hoisted():
    """The sharded serial schedule all-gathers its (loop-invariant) slab
    ONCE, outside the epoch scan. Machine check for the PR 3 trap: the
    trip-multiplicity-weighted all-gather count in the compiled HLO must
    not grow with cfg.epochs. Runs in a subprocess with 2 forced host
    devices (device count is fixed at jax init)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2")
    env["JAX_PLATFORMS"] = "cpu"
    src_root = os.path.join(os.path.dirname(__file__), "..", "..")
    env["PYTHONPATH"] = (os.path.abspath(src_root) + os.pathsep +
                         env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_HOIST_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        raise jl.InvariantViolation(
            f"sharded gather-hoist check failed:\n{proc.stdout}\n"
            f"{proc.stderr}")
    return proc.stdout.strip()


# ---------------------------------------------------------------------------
# component invariants (fault tolerance + observability, ISSUE 7)
# ---------------------------------------------------------------------------

def _faults_deterministic_replay():
    """The same FaultPlan spec against the same loop fires at the same
    site every time; kill rules raise Preemption, delay rules return (and
    with sleeper=None never wall-sleep) their seconds, and every rule is
    spent after its count."""
    from repro.distributed import faults as fm

    def drive(plan):
        visited = []
        try:
            for lvl in (3, 2, 1, 0):
                plan.site("cascade.level", level=lvl, K=2 ** lvl)
                visited.append(lvl)
        except fm.Preemption as e:
            visited.append(("kill", e.info["level"]))
        return visited

    a = drive(fm.FaultPlan().kill_at_level(1))
    b = drive(fm.FaultPlan().kill_at_level(1))
    if not (a == b == [3, 2, ("kill", 1)]):
        raise jl.InvariantViolation(
            f"fault replay is not deterministic: {a} vs {b}")
    plan = fm.FaultPlan(sleeper=None).delay_partition(2, 0.5)
    got = (plan.site("cascade.partition", partition=1),
           plan.site("cascade.partition", partition=2),
           plan.site("cascade.partition", partition=2))
    if got != (0.0, 0.5, 0.0):
        raise jl.InvariantViolation(
            f"delay rule mis-fired or was not spent: {got}")
    if plan.fired != [("delay", "cascade.partition", {"partition": 2})]:
        raise jl.InvariantViolation(f"fired log wrong: {plan.fired}")
    return "faults: deterministic replay, counts spend, virtual delays"


def _checkpoint_crash_window():
    """A kill between the fsync'd temp write and the atomic rename never
    disturbs the previously committed step, and the orphaned temp dir is
    garbage-collected by the next save."""
    import os
    import tempfile
    from repro.distributed import checkpoint as ck
    from repro.distributed import faults as fm

    with tempfile.TemporaryDirectory() as d:
        plan = fm.FaultPlan()
        mgr = ck.CheckpointManager(d, keep=3, faults=plan)
        mgr.save(1, {"a": jnp.arange(4.0)})
        plan.kill_mid_checkpoint()   # arm AFTER step 1 committed
        try:
            mgr.save(2, {"a": jnp.arange(4.0) + 1.0})
        except fm.Preemption:
            pass
        else:
            raise jl.InvariantViolation("kill_mid_checkpoint did not fire")
        if mgr.latest_step() != 1:
            raise jl.InvariantViolation(
                f"crash window corrupted the committed step: "
                f"latest={mgr.latest_step()}")
        back = mgr.restore({"a": jnp.zeros(4)})
        assert jnp.array_equal(back["a"], jnp.arange(4.0))
        orphans = [n for n in os.listdir(d) if ".tmp." in n]
        if not orphans:
            raise jl.InvariantViolation(
                "the killed writer left no orphan — the site is not in "
                "the crash window")
        mgr.save(2, {"a": jnp.arange(4.0) + 1.0})
        left = [n for n in os.listdir(d) if ".tmp." in n]
        if left:
            raise jl.InvariantViolation(f"orphans survived _gc: {left}")
    return "checkpoint: crash window safe, orphan GC'd on next save"


def _resume_cascade_bit_identical():
    """ISSUE 7 acceptance: kill the driver mid-cascade; fit(resume=)
    returns a bit-identical result with fewer level solves than a cold
    restart."""
    import tempfile

    import numpy as np

    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import kernel_fns as kf
    from repro.core import sodm
    from repro.distributed import faults as fm

    x, y = _toy_data(32, 4)
    problem = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5))
    cfg = _route_cfg("sodm")                     # levels=2 -> 3 solves
    key = jax.random.PRNGKey(0)
    _, base = ODMEstimator(problem, route="sodm", cfg=cfg).fit(x, y, key)
    with tempfile.TemporaryDirectory() as d:
        try:
            ODMEstimator(problem, route="sodm", cfg=cfg).fit(
                x, y, key, resume=d, faults=fm.FaultPlan().kill_at_level(1))
        except fm.Preemption:
            pass
        else:
            raise jl.InvariantViolation("kill_at_level(1) did not fire")
        c0 = sodm.level_solve_count()
        _, resumed = ODMEstimator(problem, route="sodm", cfg=cfg).fit(
            x, y, key, resume=d)
        ran = sodm.level_solve_count() - c0
    cold = cfg.levels + 1
    if ran >= cold:
        raise jl.InvariantViolation(
            f"resume re-ran {ran} level solves, not fewer than the cold "
            f"restart's {cold}")
    if not np.array_equal(np.asarray(resumed.raw.alpha),
                          np.asarray(base.raw.alpha)):
        raise jl.InvariantViolation("resumed duals differ bitwise")
    return f"resume(cascade): bit-identical, {ran} < {cold} level solves"


def _resume_dsvrg_segments():
    """The dsvrg route checkpoints (w, epoch) between scan segments; a
    killed-and-resumed solve is bit-identical to the uninterrupted
    segmented run."""
    import dataclasses as dc
    import tempfile

    import numpy as np

    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import kernel_fns as kf
    from repro.distributed import faults as fm

    x, y = _toy_data(32, 4)
    problem = ProblemSpec(kernel=kf.KernelSpec(name="linear"))
    cfg = _route_cfg("dsvrg")
    cfg = dc.replace(cfg, dsvrg=dc.replace(cfg.dsvrg, epochs=4))
    key = jax.random.PRNGKey(0)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        model_a, _ = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
            x, y, key, resume=d1)
        try:
            ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
                x, y, key, resume=d2, faults=fm.FaultPlan().kill_at_epoch(2))
        except fm.Preemption:
            pass
        else:
            raise jl.InvariantViolation("kill_at_epoch(2) did not fire")
        model_b, _ = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
            x, y, key, resume=d2)
    if not np.array_equal(np.asarray(model_a.w), np.asarray(model_b.w)):
        raise jl.InvariantViolation(
            "resumed dsvrg iterate differs bitwise from the "
            "uninterrupted segmented run")
    return "resume(dsvrg): killed+resumed w bitwise == uninterrupted"


def _tracker_level_stream():
    """The tracker protocol receives one record per cascade level (with
    KKT / sweeps / SV-count / throughput) plus a final fit summary, and
    the jsonl backend round-trips the stream, tolerating a torn tail
    line from a killed writer."""
    import os
    import tempfile

    from repro import observe
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import kernel_fns as kf

    x, y = _toy_data(32, 4)
    problem = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5))
    cfg = _route_cfg("sodm")
    mem = observe.InMemoryTracker()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "metrics.jsonl")
        tracker = observe.CompositeTracker(
            [mem, observe.JsonlTracker(path)])
        ODMEstimator(problem, route="sodm", cfg=cfg).fit(
            x, y, jax.random.PRNGKey(0), tracker=tracker)
        with open(path, "a") as f:
            f.write('{"step": 99, "torn')       # killed mid-line
        records = observe.read_jsonl(path)
    levels = [m for _, m in mem.steps if "level" in m]
    if len(levels) != cfg.levels + 1:
        raise jl.InvariantViolation(
            f"expected {cfg.levels + 1} per-level records, got "
            f"{len(levels)}")
    need = {"level", "kkt", "sweeps", "sv_count", "rows_per_s"}
    missing = need - set(levels[0])
    if missing:
        raise jl.InvariantViolation(f"level record missing {missing}")
    if not mem.latest().get("fit_done"):
        raise jl.InvariantViolation("no final fit summary was logged")
    if len(records) != len(mem.steps):
        raise jl.InvariantViolation(
            f"jsonl round trip lost records ({len(records)} vs "
            f"{len(mem.steps)}) or kept the torn line")
    return "tracker: per-level stream + summary, torn-tail-safe jsonl"


def _observe_zero_cost_off():
    """PR 9 span/instrument telemetry is zero-cost when off and inert
    when on: (a) with no recorder installed ``span()`` returns the shared
    no-op singleton; (b) a fit with tracker + trace_dir produces a
    bitwise-identical model and the same number of level-solve launches
    as a bare fit, and its exported trace is valid Chrome JSON with
    cascade.level spans nested inside fit; (c) re-fitting the dsvrg route
    with trace_dir adds zero new epoch-scan traces (trace-once holds
    under tracing)."""
    import dataclasses as dc
    import json
    import os
    import tempfile

    import numpy as np

    from repro import observe
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import kernel_fns as kf
    from repro.observe import spans as spans_mod

    # (a) the off path allocates nothing per call
    if spans_mod.current_recorder() is not None:
        raise jl.InvariantViolation(
            "a span recorder leaked in from a previous test")
    if observe.span("a", k=1) is not observe.span("b"):
        raise jl.InvariantViolation(
            "span() with no recorder must return the shared no-op")

    x, y = _toy_data(32, 4)
    key = jax.random.PRNGKey(0)

    # (b) sodm: instrumented fit == bare fit, launch-for-launch
    problem = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5))
    cfg = _route_cfg("sodm")
    solves = counter("sodm.level_solve")
    n0 = solves.count
    model_a, _ = ODMEstimator(problem, route="sodm", cfg=cfg).fit(
        x, y, key)
    bare_solves = solves.count - n0
    with tempfile.TemporaryDirectory() as d:
        n1 = solves.count
        model_b, _ = ODMEstimator(problem, route="sodm", cfg=cfg).fit(
            x, y, key, tracker=observe.MetricsRegistry(), trace_dir=d)
        traced_solves = solves.count - n1
        with open(os.path.join(d, "trace.json")) as f:
            trace = json.load(f)
    if traced_solves != bare_solves:
        raise jl.InvariantViolation(
            f"tracing changed the level-solve count: {bare_solves} bare "
            f"vs {traced_solves} traced")
    if not np.array_equal(np.asarray(model_a.coef),
                          np.asarray(model_b.coef)):
        raise jl.InvariantViolation(
            "model fitted under tracker+trace_dir differs bitwise from "
            "the bare fit")
    events = trace["traceEvents"]
    fits = [e for e in events if e["name"] == "fit"]
    lvls = [e for e in events if e["name"] == "cascade.level"]
    if len(fits) != 1 or not lvls:
        raise jl.InvariantViolation(
            f"expected 1 fit span and >=1 cascade.level spans, got "
            f"{len(fits)}/{len(lvls)}")
    f0 = fits[0]
    for e in lvls:
        if not (f0["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= f0["ts"] + f0["dur"]):
            raise jl.InvariantViolation(
                "cascade.level span not contained in the fit span")

    # (c) dsvrg trace-once survives tracing: a warm re-fit with trace_dir
    # must add zero epoch-scan traces
    lproblem = ProblemSpec(kernel=kf.KernelSpec(name="linear"))
    lcfg = _route_cfg("dsvrg")
    lcfg = dc.replace(lcfg, dsvrg=dc.replace(lcfg.dsvrg, epochs=2))
    traces = counter("dsvrg.epoch_trace")
    ODMEstimator(lproblem, route="dsvrg", cfg=lcfg).fit(x, y, key)  # warm
    n2 = traces.count
    with tempfile.TemporaryDirectory() as d:
        ODMEstimator(lproblem, route="dsvrg", cfg=lcfg).fit(
            x, y, key, trace_dir=d)
    if traces.count != n2:
        raise jl.InvariantViolation(
            f"trace_dir fit retraced the dsvrg epoch scan "
            f"({traces.count - n2} new traces)")
    return ("observe: off-path is the shared no-op; traced sodm fit is "
            "bitwise equal with equal launches and nested spans; dsvrg "
            "stays trace-once")


def _data_stream_loader():
    """The out-of-core data plane's contract: (a) slab contents are a
    pure function of the rows, bitwise invariant to how the source is
    sharded; (b) every shard is read exactly once per pass and the rows
    counter/depth gauge account truthfully (depth never exceeds the
    configured bound); (c) the byte accountant's peak stays below the
    dataset size for a multi-shard source (the loader never materializes
    the whole set); (d) a kill at the ``data.prefetch`` site surfaces
    out of the consuming iteration as Preemption."""
    import numpy as np

    from repro.data import streaming as ds
    from repro.distributed import faults as fm
    from repro.observe import MetricsRegistry

    rng = np.random.default_rng(0)
    M, d, slab = 96, 5, 32
    x = rng.normal(size=(M, d)).astype(np.float32)
    y = np.where(rng.random(M) < 0.5, -1.0, 1.0).astype(np.float32)

    def slabs(shard_rows):
        src = ds.ArraySource(x, y, shard_rows=shard_rows)
        acct = ds.ByteAccountant()
        mets = MetricsRegistry()
        out = [(np.asarray(s.x).copy(), np.asarray(s.y).copy(), s.n_valid)
               for s in ds.iter_slabs(src, slab, depth=2, metrics=mets,
                                      executor=ds.SerialExecutor(),
                                      accountant=acct)]
        return src, acct, mets, out

    src_a, acct, mets, a = slabs(16)
    _, _, _, b = slabs(24)          # misaligned: shards straddle slabs
    for (xa, ya, na), (xb, yb, nb) in zip(a, b, strict=True):
        if not (np.array_equal(xa, xb) and np.array_equal(ya, yb)
                and na == nb):
            raise jl.InvariantViolation(
                "slab contents depend on the shard layout — streaming "
                "results would not be reproducible across re-sharding")
    if src_a.reads != [1] * len(src_a.reads):
        raise jl.InvariantViolation(
            f"one pass must read each shard exactly once: {src_a.reads}")
    snap = mets.snapshot()
    if snap.get("data.rows.count") != M:
        raise jl.InvariantViolation(
            f"rows counter lies: {snap.get('data.rows.count')} != {M}")
    if snap.get("data.prefetch.depth.max", 0) > 2:
        raise jl.InvariantViolation(
            f"prefetch queue exceeded its depth bound: "
            f"{snap['data.prefetch.depth.max']} > 2")
    if snap.get("data.shard.read_s.count") != len(src_a.reads):
        raise jl.InvariantViolation(
            f"shard-read histogram count "
            f"{snap.get('data.shard.read_s.count')} != shard count")
    if not 0 < acct.peak < src_a.total_bytes:
        raise jl.InvariantViolation(
            f"accountant peak {acct.peak} not inside (0, "
            f"{src_a.total_bytes}) — the loader materialized the set")
    plan = fm.FaultPlan().kill("data.prefetch", shard=2)
    src_c = ds.ArraySource(x, y, shard_rows=16)
    try:
        for _ in ds.iter_slabs(src_c, slab, faults=plan,
                               executor=ds.SerialExecutor()):
            pass
    except fm.Preemption as e:
        if e.info.get("shard") != 2:
            raise jl.InvariantViolation(f"kill struck shard {e.info}")
    else:
        raise jl.InvariantViolation(
            "a data.prefetch kill never surfaced from the iteration")
    return ("data: slabs layout-invariant, single-read passes, honest "
            "gauges, bounded resident bytes, kills propagate")


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------

def _declare_builtins() -> None:
    kern = [
        ("kernels.gram.single_launch", "gram",
         "one pallas_call per Gram build", _gram_single_launch),
        ("kernels.gram.vmem_plan", "gram",
         "default (and laplacian) tile plans fit the VMEM budget",
         _gram_vmem),
        ("kernels.gram_matvec.single_launch", "gram_matvec",
         "one pallas_call for all K partition matvecs",
         _gram_matvec_single_launch),
        ("kernels.gram_matvec.vmem_plan", "gram_matvec",
         "default tile plan fits the VMEM budget", _gram_matvec_vmem),
        ("kernels.score.single_launch", "score",
         "one pallas_call per request batch", _score_single_launch),
        ("kernels.score.gather_free", "score",
         "served score paths contain no gather and no host sync",
         _score_gather_free),
        ("kernels.score.vmem_plan", "score",
         "default tile plan fits the VMEM budget", _score_vmem),
        ("kernels.odm_grad.single_launch", "odm_grad",
         "full primal gradient in one pallas_call",
         _odm_grad_single_launch),
        ("kernels.odm_grad.vmem_plan", "odm_grad",
         "_shrink_bm keeps every feature width inside the VMEM budget",
         _odm_grad_vmem),
        ("kernels.odm_svrg_grad.single_launch", "odm_svrg_grad",
         "one pallas_call per DSVRG inner step", _svrg_grad_single_launch),
        ("kernels.odm_svrg_grad.vmem_plan", "odm_svrg_grad",
         "default tile plan fits the VMEM budget", _svrg_grad_vmem),
        ("kernels.fused_cd.single_launch", "fused_cd",
         "one pallas_call per fused sweep, dense and matrix-free",
         _fused_cd_single_launch),
        ("kernels.fused_cd.vmem_plan", "fused_cd",
         "default plans (both sources) fit the VMEM budget",
         _fused_cd_vmem),
        ("kernels.fused_cd.vmem_ceiling", "fused_cd",
         "the m=2*10^6 partition-resident u_d plan is REJECTED at plan "
         "time with a sizing report", _fused_cd_vmem_ceiling),
    ]
    for name, subject, desc, fn in kern:
        declare(Invariant(name=name, subject=subject, kind="kernel",
                          description=desc, verify=fn))

    for route in ("sodm", "dsvrg", "cascade", "dip", "dc", "svrg",
                  "csvrg"):
        declare(Invariant(
            name=f"routes.{route}.facade_artifact", subject=route,
            kind="route",
            description="ODMEstimator.fit returns a FittedODM with no "
                        "legacy FutureWarning",
            verify=_make_facade_invariant(route)))

    declare(Invariant(
        name="routes.sodm.predict_gather_once", subject="sodm",
        kind="route",
        description="one perm gather per fitted model, zero per predict",
        verify=_sodm_gather_once))
    declare(Invariant(
        name="routes.dsvrg.trace_once", subject="dsvrg", kind="route",
        description="identical re-solve adds zero jit traces",
        verify=_dsvrg_trace_once))
    declare(Invariant(
        name="routes.dsvrg.epoch_scan_shape", subject="dsvrg",
        kind="route",
        description="one epoch scan, no collectives/host-sync in loop "
                    "bodies of the local driver",
        verify=_dsvrg_epoch_scan_shape))
    declare(Invariant(
        name="routes.dsvrg.sharded_gather_hoisted", subject="dsvrg",
        kind="route", slow=True,
        description="serial-schedule slab all-gather count in compiled "
                    "HLO is epoch-count-invariant (hoisted above the "
                    "scan)",
        verify=_dsvrg_sharded_gather_hoisted))

    comp = [
        ("components.faults.deterministic_replay", "faults",
         "fault plans replay deterministically; kills raise, delays "
         "return seconds, counts spend", _faults_deterministic_replay),
        ("components.checkpoint.crash_window", "checkpoint",
         "a kill in the write/rename window keeps the previous step "
         "loadable and the orphan is GC'd on the next save",
         _checkpoint_crash_window),
        ("components.resume.cascade_bit_identical", "resume",
         "kill-mid-cascade + fit(resume=) is bit-identical with fewer "
         "level solves than a cold restart",
         _resume_cascade_bit_identical),
        ("components.resume.dsvrg_segments", "resume",
         "dsvrg segment checkpoints make killed+resumed bitwise equal "
         "to the uninterrupted segmented run", _resume_dsvrg_segments),
        ("components.tracker.level_stream", "tracker",
         "per-level KKT/sweeps/SV/throughput records + fit summary; "
         "jsonl backend is torn-tail-safe", _tracker_level_stream),
        ("components.observe.zero_cost_off", "observe",
         "spans/instruments are no-ops when off; tracing a fit keeps it "
         "bitwise identical, launch-for-launch, and dsvrg trace-once",
         _observe_zero_cost_off),
        ("components.data.stream_loader", "data",
         "slabs are bitwise layout-invariant; one read per shard per "
         "pass; depth/rows instruments honest; resident bytes bounded "
         "below the dataset; prefetch kills propagate",
         _data_stream_loader),
    ]
    for name, subject, desc, fn in comp:
        declare(Invariant(name=name, subject=subject, kind="component",
                          description=desc, verify=fn))


_declare_builtins()
