"""Capability-based solver registry — every training route behind one door.

The paper presents SODM as ONE method with two regimes: hierarchical
partitioned dual solves for nonlinear kernels (Algorithm 1) and a
communication-efficient SVRG for the linear kernel (Algorithm 2). The
repo's Section-4 baselines add five more strategies. Each route registers
a :class:`SolverEntry` here with *declared capabilities* — supported
kernel families, mesh-awareness, matrix-free-ness, scale band — and one
:func:`resolve` policy turns (problem, M, mesh[, route/config]) into the
entry that trains it:

* an EXPLICIT choice always wins: ``resolve(..., route=name)`` returns
  that entry or raises a ``ValueError`` listing its capabilities when the
  problem is outside them (never a silent fallback);
* the AUTO policy (``route=None``) is the paper's dispatch
  (property-tested in ``tests/test_api.py``): a ``SODMConfig.engine``
  pinned to a level engine stays on the ``sodm`` route whatever the
  problem size; ``engine="dsvrg"`` demands the dsvrg route (linear
  kernel required); an unset engine routes linear-kernel problems with
  M >= ``dsvrg_threshold`` to ``dsvrg`` and everything else to ``sodm``.

Routes (see also the README table):

====== ===================================================== =========
name   strategy                                              kernels
====== ===================================================== =========
sodm   Alg. 1 hierarchical partitioned dual CD               all
dsvrg  Alg. 2 communication-efficient primal SVRG            linear
cascade Graf et al. 2004 binary-funnel cascade (Ca-ODM)      all
dip    DiP-SVM-style round-robin k-means strata (DiP-ODM)    all
dc     DC-SVM-style cluster-per-partition (DC-ODM)           all
svrg   single-chain SVRG (Johnson & Zhang 2013)              linear
csvrg  coreset-anchor SVRG (Tan et al. 2019)                 linear
====== ===================================================== =========

Every ``fit`` callable has the uniform signature

    fit(problem, x, y, key, *, cfg, mesh, data_axis, auto,
        compile_kw, fit_kw) -> RouteOutput

and returns a compiled, deployable :class:`repro.serve.model.FittedODM`
plus the report fields — training output is ALWAYS a servable artifact.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import baselines as baselines_mod
from repro.core import dsvrg as dsvrg_mod
from repro.core import engines
from repro.core import odm as odm_mod
from repro.core import sodm as sodm_mod
from repro.core.odm import ODMParams
from repro.observe import span
from repro.serve import model as serve_model

Array = jax.Array

#: auto-dispatch threshold of Algorithm 2 ("when linear kernel is
#: applied ... we extend a communication efficient SVRG method") — read
#: off ``SODMConfig.dsvrg_threshold``'s default so bare registry
#: resolution and config-carrying resolution can never disagree.
DSVRG_AUTO_THRESHOLD = sodm_mod.SODMConfig.dsvrg_threshold


class RouteOutput(NamedTuple):
    """What a route's ``fit`` hands back to the estimator."""

    model: serve_model.FittedODM
    raw: object                       # the route's native result
    engine: str
    passes: tuple[int, ...]
    kkt: float | None = None
    eta: float | None = None
    history: tuple[float, ...] | None = None


@dataclasses.dataclass(frozen=True)
class SolverEntry:
    """One registered training route and its declared capabilities."""

    name: str
    fit: Callable[..., RouteOutput]
    algorithm: str                     # paper algorithm / citation
    kernels: frozenset[str] | None = None   # None = every KernelSpec family
    mesh_aware: bool = False           # has an SPMD (shard_map) driver
    matrix_free: bool = False          # never materializes O(m^2) state
    streaming: bool = False            # consumes a ShardedSource out-of-core
    scale_min: int = 0                 # auto-dispatch band (advisory)
    scale_max: int | None = None
    description: str = ""

    def capabilities(self) -> str:
        """Human-readable capability line (used by every resolve error)."""
        kern = "all kernels" if self.kernels is None \
            else "kernels {" + ", ".join(sorted(self.kernels)) + "}"
        band = f"M in [{self.scale_min}, " + \
            (f"{self.scale_max}]" if self.scale_max is not None else "inf)")
        return (f"{self.name}: {self.algorithm}; {kern}; "
                f"mesh_aware={self.mesh_aware}; "
                f"matrix_free={self.matrix_free}; "
                f"streaming={self.streaming}; {band}")

    def check(self, kernel_name: str, M: int,
              mesh: jax.sharding.Mesh | None = None,
              streaming: bool = False) -> None:
        """Raise ``ValueError`` (listing capabilities) on incompatibility."""
        if self.kernels is not None and kernel_name not in self.kernels:
            raise ValueError(
                f"route {self.name!r} does not support kernel "
                f"{kernel_name!r} — its capabilities: {self.capabilities()}."
                f" Routes supporting {kernel_name!r}: "
                f"{supporting(kernel_name)}")
        if mesh is not None and not self.mesh_aware:
            raise ValueError(
                f"route {self.name!r} has no SPMD driver but a mesh was "
                f"given — its capabilities: {self.capabilities()}. "
                f"Mesh-aware routes: "
                f"{[e.name for e in _REGISTRY.values() if e.mesh_aware]}")
        if streaming and not self.streaming:
            raise ValueError(
                f"route {self.name!r} cannot train from a ShardedSource — "
                f"its capabilities: {self.capabilities()}. Streaming routes: "
                f"{streaming_routes()}")
        if streaming and mesh is not None:
            raise ValueError(
                "streaming fits have no SPMD driver yet (ROADMAP open "
                "item 2: mesh-sharded shard ingestion) — drop the mesh or "
                "materialize the source")


_REGISTRY: dict[str, SolverEntry] = {}


def register(entry: SolverEntry) -> SolverEntry:
    """Add a route. Duplicate names raise (no silent shadowing)."""
    if entry.name in _REGISTRY:
        raise ValueError(
            f"route {entry.name!r} is already registered "
            f"({_REGISTRY[entry.name].capabilities()}); unregister it "
            f"first or pick another name. Registered routes: {routes()}")
    _REGISTRY[entry.name] = entry
    return entry


def unregister(name: str) -> None:
    """Remove a route (plugin/test hook)."""
    _REGISTRY.pop(name, None)


def get(name: str) -> SolverEntry:
    """Look a route up by name; unknown names raise listing the options."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown route {name!r}; registered routes: {routes()}"
        ) from None


def routes() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def supporting(kernel_name: str) -> list[str]:
    """Route names whose capabilities cover ``kernel_name``."""
    return [e.name for e in _REGISTRY.values()
            if e.kernels is None or kernel_name in e.kernels]


def streaming_routes() -> list[str]:
    """Route names that can consume a ShardedSource out-of-core."""
    return [e.name for e in _REGISTRY.values() if e.streaming]


def capability_table() -> str:
    """All routes, one capability line each (README / error helper)."""
    return "\n".join(_REGISTRY[n].capabilities() for n in routes())


# ---------------------------------------------------------------------------
# resolution policy
# ---------------------------------------------------------------------------

def resolve(problem, M: int, mesh: jax.sharding.Mesh | None = None,
            route: str | None = None, cfg=None,
            streaming: bool = False) -> SolverEntry:
    """The one dispatch policy: explicit route wins, else the paper's auto
    rule. ``problem`` is a :class:`repro.api.spec.ProblemSpec` (or a bare
    ``KernelSpec``); ``cfg`` an optional ``SODMConfig`` supplying the
    ``engine`` pin and ``dsvrg_threshold``; ``streaming`` marks a fit fed
    by a ShardedSource (routes without an out-of-core driver refuse).
    """
    kernel_name = getattr(getattr(problem, "kernel", problem), "name")
    if route is not None:
        entry = get(route)
        if entry.name != "dsvrg" and getattr(cfg, "engine", None) == "dsvrg":
            raise ValueError(
                f"route={route!r} with SODMConfig.engine='dsvrg' is "
                f"contradictory — use route='dsvrg', or leave route unset "
                f"(the resolve policy honors the engine pin)")
        entry.check(kernel_name, M, mesh, streaming)
        return entry
    engine = getattr(cfg, "engine", None)
    threshold = getattr(cfg, "dsvrg_threshold", DSVRG_AUTO_THRESHOLD)
    return resolve_auto(kernel_name, M, engine=engine, threshold=threshold,
                        mesh=mesh, streaming=streaming)


def resolve_auto(kernel_name: str, M: int, *, engine: str | None = None,
                 threshold: int = DSVRG_AUTO_THRESHOLD,
                 mesh: jax.sharding.Mesh | None = None,
                 streaming: bool = False) -> SolverEntry:
    """The paper's linear-kernel dispatch (Section 3.3).

    ``engine="dsvrg"`` demands the dsvrg route (raises for nonlinear
    kernels, listing capabilities); any other explicitly named engine —
    scalar included — pins the sodm level loop whatever the problem size;
    an unset engine (``None``) routes linear-kernel problems with
    M >= ``threshold`` to dsvrg and everything else to sodm.

    Streaming fits narrow the menu to the out-of-core drivers: linear
    kernels (or an explicit dsvrg engine pin) stream through dsvrg —
    a source is by definition past the threshold regime — and every
    other kernel streams through the cascade.
    """
    if streaming:
        if engine == "dsvrg" or kernel_name == "linear":
            entry = get("dsvrg")
        else:
            entry = get("cascade")
    elif engine == "dsvrg":
        entry = get("dsvrg")
    elif engine is None and kernel_name == "linear" and M >= threshold:
        entry = get("dsvrg")
    else:
        entry = get("sodm")
    entry.check(kernel_name, M, mesh, streaming)
    return entry


def dsvrg_partition_count(M: int, want: int, n_dev: int = 1) -> int:
    """Largest K <= ``want`` that divides M and is a multiple of ``n_dev``
    (the dsvrg route's partition clamp, shared by every caller)."""
    K = max(want - want % n_dev, n_dev)
    while K >= n_dev:
        if M % K == 0:
            return K
        K -= n_dev
    raise ValueError(
        f"no DSVRG partition count <= {want} divides M={M} and is a "
        f"multiple of the data axis size {n_dev}")


def _solve_dsvrg(x: Array, y: Array, params: ODMParams,
                 cfg: sodm_mod.SODMConfig, key: jax.Array,
                 mesh: jax.sharding.Mesh | None = None,
                 data_axis: str = "data", auto: bool = False, *,
                 faults=None, tracker=None, resume=None,
                 ) -> tuple[sodm_mod.SODMResult, dsvrg_mod.DSVRGResult]:
    """The dsvrg route's resident solve (Algorithm 2 on the whole problem).

    Solves the primal with DSVRG and recovers the dual via
    ``odm.alpha_from_w``; the native ``DSVRGResult`` is returned alongside
    so the route can report the objective history / eta and compile the
    artifact from the primal ``w`` directly. ``levels_run`` is 1 (a single
    whole-problem solve), ``sweeps_per_level`` reports the epoch count,
    and ``kkt`` is the primal gradient infinity norm (the natural
    stationarity residual of the primal path). The outer
    ``partition_strategy``/``n_landmarks`` carry over when DSVRG supports
    the strategy (stratified/random; cluster/identity keep ``cfg.dsvrg``'s
    own setting). The solve is epoch-budgeted (``cfg.dsvrg.epochs``) —
    ``tol``/``max_sweeps`` are level-loop knobs and do not apply here;
    check the returned ``kkt`` if a stationarity guarantee is needed. An
    AUTO-dispatched solve on a mesh (``auto=True``) upgrades the default
    serial schedule to ``"parallel"``: the serial chain is replicated
    compute over an all-gathered slab — the right validation tool, but
    exactly wrong for the big-data regime that triggers the auto route.
    An explicit ``engine="dsvrg"`` or ``route="dsvrg"`` keeps whatever
    ``cfg.dsvrg`` says.
    """
    M = x.shape[0]
    n_dev = mesh.shape[data_axis] if mesh is not None else 1
    K = dsvrg_partition_count(M, cfg.dsvrg.n_partitions, n_dev)
    dcfg = dataclasses.replace(cfg.dsvrg, n_partitions=K)
    if auto and mesh is not None:
        dcfg = dataclasses.replace(dcfg, schedule="parallel")
    if cfg.partition_strategy in ("stratified", "random"):
        dcfg = dataclasses.replace(
            dcfg, partition_strategy=cfg.partition_strategy,
            n_landmarks=cfg.n_landmarks)
    if mesh is not None:
        res = dsvrg_mod._solve_sharded(x, y, params, dcfg, key, mesh,
                                       data_axis=data_axis, faults=faults,
                                       tracker=tracker, resume=resume)
    else:
        res = dsvrg_mod._solve(x, y, params, dcfg, key, faults=faults,
                               tracker=tracker, resume=resume)
    xp, yp = x[res.perm], y[res.perm]
    alpha = odm_mod.alpha_from_w(res.w, xp, yp, params)
    # grad p(w) = w - w_from_alpha(alpha_from_w(w)) exactly (the recovered
    # dual's hinge coefficient is -y⊙(zeta-beta)), so the stationarity
    # residual reuses the alpha pass instead of a second O(M·d) sweep
    kkt = jnp.max(jnp.abs(res.w - odm_mod.w_from_alpha(xp, yp, alpha)))
    return sodm_mod.SODMResult(alpha=alpha, perm=res.perm, levels_run=1,
                               sweeps_per_level=[dcfg.epochs], kkt=kkt), res


# ---------------------------------------------------------------------------
# route implementations (uniform fit signature)
# ---------------------------------------------------------------------------

def _hooks(fit_kw) -> dict:
    """The preemption/observability seams every instrumented route takes
    (repro.distributed.faults / repro.observe / repro.distributed.resume),
    forwarded from ``ODMEstimator.fit(faults=, tracker=, resume=)``."""
    return {k: fit_kw[k] for k in ("faults", "tracker", "resume")
            if fit_kw.get(k) is not None}


def _stream_hooks(fit_kw) -> dict:
    """:func:`_hooks` plus the loader knobs only the streaming drivers
    take: prefetch ``depth``, injected ``executor``/``metrics`` (chaos
    and instrument tests), and the resident-byte ``accountant``."""
    kw = _hooks(fit_kw)
    kw.update({k: fit_kw[k]
               for k in ("depth", "executor", "metrics", "accountant")
               if fit_kw.get(k) is not None})
    return kw


def _fit_sodm(problem, x, y, key, *, cfg, mesh, data_axis, auto,
              compile_kw, fit_kw) -> RouteOutput:
    del auto
    if mesh is None:
        res = sodm_mod._solve(problem.kernel, x, y, problem.params, cfg,
                              key, fit_kw.get("level_callback"),
                              **_hooks(fit_kw))
    else:
        res = sodm_mod._solve_sharded(problem.kernel, x, y, problem.params,
                                      cfg, key, mesh, data_axis=data_axis,
                                      **_hooks(fit_kw))
    with span("fit.artifact"):
        model = serve_model.from_sodm(problem.kernel, res, x, y,
                                      **compile_kw)
    return RouteOutput(model=model, raw=res,
                       engine=engines.level_engine(cfg.engine),
                       passes=tuple(res.sweeps_per_level),
                       kkt=float(res.kkt))


def _fit_dsvrg(problem, x, y, key, *, cfg, mesh, data_axis, auto,
               compile_kw, fit_kw) -> RouteOutput:
    if y is None:                      # x is a ShardedSource (streaming fit)
        del mesh, data_axis, auto, compile_kw
        source = x
        dres, kkt = dsvrg_mod._solve_stream(source, problem.params,
                                            cfg.dsvrg, key,
                                            **_stream_hooks(fit_kw))
        # the dual-recovery pass of the resident path is O(M) host state —
        # a streaming fit compiles the artifact straight from the primal w
        model = serve_model.FittedODM(spec=problem.kernel, w=dres.w,
                                      n_train=int(source.n_rows),
                                      compression="linear")
        return RouteOutput(model=model, raw=dres, engine="dsvrg",
                           passes=(len(dres.history),), kkt=float(kkt),
                           eta=float(dres.eta),
                           history=tuple(float(h) for h in dres.history))
    res, dres = _solve_dsvrg(x, y, problem.params, cfg, key, mesh=mesh,
                             data_axis=data_axis, auto=auto,
                             **_hooks(fit_kw))
    # the artifact comes straight from the primal w (born compressed, and
    # bit-identical to serve.from_dsvrg of the same solve); the
    # recovered-dual SODMResult rides along as the stationarity check
    model = dataclasses.replace(serve_model.from_dsvrg(dres),
                                spec=problem.kernel)
    return RouteOutput(model=model, raw=dres, engine="dsvrg",
                       passes=(len(dres.history),), kkt=float(res.kkt),
                       eta=float(dres.eta),
                       history=tuple(float(h) for h in dres.history))


def _fit_cascade(problem, x, y, key, *, cfg, mesh, data_axis, auto,
                 compile_kw, fit_kw) -> RouteOutput:
    del mesh, data_axis, auto
    if y is None:                      # x is a ShardedSource (streaming fit)
        res = baselines_mod._cascade_solve_stream(
            problem.kernel, x, problem.params, levels=cfg.levels, key=key,
            tol=cfg.tol, max_sweeps=cfg.max_sweeps, **_stream_hooks(fit_kw))
    else:
        del fit_kw
        res = baselines_mod._cascade_solve(problem.kernel, x, y,
                                           problem.params, levels=cfg.levels,
                                           key=key, tol=cfg.tol,
                                           max_sweeps=cfg.max_sweeps)
    model = serve_model.from_cascade(problem.kernel, res, **compile_kw)
    return RouteOutput(model=model, raw=res, engine="scalar",
                       passes=(res.levels_run,))


def _fit_dip(problem, x, y, key, *, cfg, mesh, data_axis, auto,
             compile_kw, fit_kw) -> RouteOutput:
    del mesh, data_axis, auto, fit_kw
    res = baselines_mod._dip_solve(problem.kernel, x, y, problem.params,
                                   cfg, key)
    model = serve_model.from_sodm(problem.kernel, res, x, y, **compile_kw)
    return RouteOutput(model=model, raw=res,
                       engine=engines.level_engine(cfg.engine),
                       passes=tuple(res.sweeps_per_level),
                       kkt=float(res.kkt))


def _fit_dc(problem, x, y, key, *, cfg, mesh, data_axis, auto,
            compile_kw, fit_kw) -> RouteOutput:
    del mesh, data_axis, auto, fit_kw
    res = baselines_mod._dc_solve(problem.kernel, x, y, problem.params,
                                  cfg, key)
    model = serve_model.from_sodm(problem.kernel, res, x, y, **compile_kw)
    return RouteOutput(model=model, raw=res,
                       engine=engines.level_engine(cfg.engine),
                       passes=tuple(res.sweeps_per_level),
                       kkt=float(res.kkt))


def _grad_eta(x, cfg, params) -> float:
    d = cfg.dsvrg
    return d.eta if d.eta > 0 else dsvrg_mod.auto_eta(x, params)


def _fit_svrg(problem, x, y, key, *, cfg, mesh, data_axis, auto,
              compile_kw, fit_kw) -> RouteOutput:
    del mesh, data_axis, auto, compile_kw, fit_kw
    d = cfg.dsvrg
    eta = _grad_eta(x, cfg, problem.params)
    res = baselines_mod._svrg_solve(x, y, problem.params, epochs=d.epochs,
                                    eta=eta, key=key, batch=d.batch)
    model = serve_model.FittedODM(spec=problem.kernel, w=res.w,
                                  n_train=int(x.shape[0]),
                                  compression="linear")
    return RouteOutput(model=model, raw=res, engine="svrg",
                       passes=(d.epochs,), eta=float(eta),
                       history=tuple(float(h) for h in res.history))


def _fit_csvrg(problem, x, y, key, *, cfg, mesh, data_axis, auto,
               compile_kw, fit_kw) -> RouteOutput:
    del mesh, data_axis, auto, compile_kw, fit_kw
    d = cfg.dsvrg
    eta = _grad_eta(x, cfg, problem.params)
    res = baselines_mod._csvrg_solve(x, y, problem.params, epochs=d.epochs,
                                     eta=eta, key=key,
                                     coreset_frac=d.coreset_frac,
                                     batch=d.batch)
    model = serve_model.FittedODM(spec=problem.kernel, w=res.w,
                                  n_train=int(x.shape[0]),
                                  compression="linear")
    return RouteOutput(model=model, raw=res, engine="csvrg",
                       passes=(d.epochs,), eta=float(eta),
                       history=tuple(float(h) for h in res.history))


# ---------------------------------------------------------------------------
# the built-in routes
# ---------------------------------------------------------------------------

_LINEAR = frozenset({"linear"})

register(SolverEntry(
    name="sodm", fit=_fit_sodm,
    algorithm="Alg. 1 (hierarchical partitioned dual CD)",
    kernels=None, mesh_aware=True, matrix_free=True,
    description="stratified partitions, warm-started level merges; level "
                "engines scalar | block | pallas"))
register(SolverEntry(
    name="dsvrg", fit=_fit_dsvrg,
    algorithm="Alg. 2 (communication-efficient SVRG)",
    kernels=_LINEAR, mesh_aware=True, matrix_free=True, streaming=True,
    scale_min=DSVRG_AUTO_THRESHOLD,
    description="primal round-robin SVRG; dual recovered via "
                "odm.alpha_from_w; auto-selected for big linear problems; "
                "accepts a ShardedSource (out-of-core epochs)"))
register(SolverEntry(
    name="cascade", fit=_fit_cascade,
    algorithm="Ca-ODM (Graf et al. 2004 cascade)",
    kernels=None, mesh_aware=False, matrix_free=False, streaming=True,
    description="binary support-vector funnel; fast but lossy baseline; "
                "accepts a ShardedSource (leaves train as shards arrive)"))
register(SolverEntry(
    name="dip", fit=_fit_dip,
    algorithm="DiP-ODM (Singh et al. 2017)",
    kernels=None, mesh_aware=False, matrix_free=False,
    description="k-means strata dealt round-robin, then the SODM merge"))
register(SolverEntry(
    name="dc", fit=_fit_dc,
    algorithm="DC-ODM (Hsieh et al. 2014)",
    kernels=None, mesh_aware=False, matrix_free=False,
    description="k-means clusters as partitions, then the SODM merge"))
register(SolverEntry(
    name="svrg", fit=_fit_svrg,
    algorithm="single-chain SVRG (Johnson & Zhang 2013)",
    kernels=_LINEAR, mesh_aware=False, matrix_free=False,
    description="gradient baseline; eta <= 0 takes the auto smoothness "
                "step"))
register(SolverEntry(
    name="csvrg", fit=_fit_csvrg,
    algorithm="coreset SVRG (Tan et al. 2019)",
    kernels=_LINEAR, mesh_aware=False, matrix_free=False,
    description="anchor gradients on a k-center coreset "
                "(DSVRGConfig.coreset_frac)"))
