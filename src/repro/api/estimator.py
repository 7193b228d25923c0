"""``ODMEstimator`` — the one front door for training and serving ODMs.

    est = ODMEstimator(ProblemSpec.create("rbf", gamma=0.5, lam=100.0))
    model, report = est.fit(x, y, jax.random.PRNGKey(0))
    est.predict(x_test)              # or model.predict(...)
    est.save("/tmp/model"); ODMEstimator.load("/tmp/model")

One estimator covers every training route in the solver registry
(:mod:`repro.api.registry`): the paper's two regimes (Alg. 1 partitioned
dual solves, Alg. 2 linear-kernel DSVRG) and the Section-4 baselines.
``fit`` validates the data once (:meth:`ProblemSpec.validate`), resolves
the route (explicit ``route=`` always wins; otherwise the registry's auto
policy — the paper's linear-kernel dispatch), runs it, and ALWAYS returns
a deployable :class:`repro.serve.model.FittedODM` plus a uniform
:class:`repro.api.report.FitReport`, whatever the route.

Persistence delegates to the serving subsystem: :meth:`save` writes the
compiled artifact through ``CheckpointManager`` (atomic, versioned) and
:meth:`load` restores an estimator that scores without refitting.
"""
from __future__ import annotations

import time

import jax

from repro.api import registry
from repro.api.report import FitReport
from repro.api.spec import ProblemSpec
from repro.core import kernel_fns as kf
from repro.core import odm as odm_mod
from repro.core.sodm import SODMConfig
from repro.observe import profile_ctx, span, trace_ctx
from repro.serve import model as serve_model

Array = jax.Array


class ODMEstimator:
    """Facade over the solver registry with sklearn-flavored verbs.

    Parameters
    ----------
    problem: what to solve — a :class:`ProblemSpec` (a bare ``KernelSpec``
        is accepted and wrapped with default ``ODMParams``); ``None``
        means the default rbf problem.
    route: registry route name, or ``None`` for the auto policy
        (:func:`repro.api.registry.resolve`). Unknown names fail HERE,
        not at fit time.
    cfg: one ``SODMConfig`` configures every route — the hierarchical
        routes read p/levels/tol/engine/..., the gradient routes read
        ``cfg.dsvrg`` (epochs/batch/eta/coreset_frac), cascade reads
        levels/tol/max_sweeps.
    mesh / data_axis: SPMD placement for the mesh-aware routes.
    prune_tol / budget / target: artifact compression knobs forwarded to
        ``serve.compile_model`` (SV pruning + Nyström landmark budget).
    """

    def __init__(self, problem: ProblemSpec | kf.KernelSpec | None = None,
                 *, route: str | None = None,
                 cfg: SODMConfig | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 data_axis: str = "data", prune_tol: float = 0.0,
                 budget: int | None = None, target: float | None = None):
        if problem is None:
            problem = ProblemSpec()
        elif isinstance(problem, kf.KernelSpec):
            problem = ProblemSpec(kernel=problem)
        self.problem = problem
        if route is not None:
            registry.get(route)            # unknown route: fail eagerly
        self.route = route
        self.cfg = cfg if cfg is not None else SODMConfig()
        self.mesh = mesh
        self.data_axis = data_axis
        self.compile_kw = {"prune_tol": prune_tol, "budget": budget,
                           "target": target}
        self.model_: serve_model.FittedODM | None = None
        self.report_: FitReport | None = None

    # -- training -----------------------------------------------------------

    #: routes with a resume/faults/tracker seam (the paper's two regimes;
    #: the Section-4 baselines have no mid-solve state worth persisting)
    INSTRUMENTED_ROUTES = ("dsvrg", "sodm")
    #: same seam on the streaming path — the cascade gains it there (its
    #: merge stack checkpoints per leaf as shards arrive)
    STREAM_INSTRUMENTED_ROUTES = ("dsvrg", "cascade")

    def fit(self, x, y: Array | None = None, key: jax.Array | None = None,
            *, resume=None, faults=None, tracker=None, profile_dir=None,
            trace_dir=None,
            **fit_kw) -> tuple[serve_model.FittedODM, FitReport]:
        """Train through the resolved route; returns (artifact, report).

        ``x`` is either a dense ``(M, d)`` feature matrix with ``y`` its
        ±1 labels, or a :class:`repro.data.streaming.ShardedSource` (with
        ``y`` omitted — a source carries its own labels). A source
        streams through an out-of-core route (dsvrg for linear kernels,
        cascade otherwise; see ``registry.streaming_routes``) without
        ever materializing the (M, d) matrix.

        Preemption-proofing and observability (sodm / dsvrg routes only —
        other routes raise rather than silently ignore these):

        resume: a directory (or :class:`repro.distributed.resume
            .ResumeConfig`) holding mid-solve checkpoints. A fresh
            directory is populated as the solve progresses (per cascade
            level / per DSVRG epoch segment); a directory left behind by
            a preempted fit restarts at the first unsolved level, and the
            result is bit-identical to an uninterrupted run. Provenance
            (kernel/params/cfg/data/key) is fingerprinted — resuming
            against a different problem raises.
        faults: a :class:`repro.distributed.faults.FaultPlan` for
            deterministic chaos testing (kill-at-level-k,
            kill-mid-checkpoint, ...).
        tracker: anything with ``log_metrics(step, dict)`` (see
            :mod:`repro.observe`); receives per-level / per-segment
            training metrics plus one final fit summary.
        profile_dir: write a JAX profiler trace of the solve there.
        trace_dir: record host-side spans (fit → route → cascade.level /
            dsvrg.pass, compiles, checkpoint commits) and export
            Chrome-trace JSON to ``<trace_dir>/trace.json`` — open it in
            Perfetto. With ``profile_dir`` too, the same spans appear on
            the host plane of the profiler trace, on its clock. Unlike
            resume/faults/tracker this works on every route (it only
            wraps host code).

        Remaining ``fit_kw`` forward route-specific hooks (currently
        ``level_callback`` for the sodm route's legacy per-level
        checkpointing seam).
        """
        from repro.data.streaming import is_source
        streaming = is_source(x)
        if streaming:
            if y is not None:
                raise ValueError(
                    "fit(source) carries its own labels — passing y "
                    "alongside a ShardedSource is ambiguous; drop y")
            self.problem.validate_source(x)
            M = int(x.n_rows)
        else:
            x, y = self.problem.validate(x, y)
            M = int(x.shape[0])
        key = jax.random.PRNGKey(0) if key is None else key
        entry = registry.resolve(self.problem, M, mesh=self.mesh,
                                 route=self.route, cfg=self.cfg,
                                 streaming=streaming)
        instrumented = self.STREAM_INSTRUMENTED_ROUTES if streaming \
            else self.INSTRUMENTED_ROUTES
        if entry.name not in instrumented:
            bad = [n for n, v in (("resume", resume), ("faults", faults),
                                  ("tracker", tracker)) if v is not None]
            if bad:
                raise ValueError(
                    f"route {entry.name!r} has no {'/'.join(bad)} seam — "
                    f"instrumented routes: {list(instrumented)}")
        if not streaming:
            loader_kw = [k for k in ("depth", "executor", "metrics",
                                     "accountant") if k in fit_kw]
            if loader_kw:
                raise ValueError(
                    f"{'/'.join(loader_kw)} are streaming loader knobs — "
                    f"they only apply to fit(source); a dense fit has no "
                    f"prefetch loader to configure")
        if resume is not None:
            fit_kw["resume"] = self._resume_manager(entry.name, resume,
                                                    x, y, key, faults,
                                                    streaming=streaming)
        if faults is not None:
            fit_kw["faults"] = faults
        if tracker is not None:
            fit_kw["tracker"] = tracker
        # the schedule-upgrade rule only applies to AUTO dsvrg dispatch
        # (an explicit choice keeps whatever cfg.dsvrg says)
        auto = (entry.name == "dsvrg" and self.route is None
                and self.cfg.engine != "dsvrg")
        t0 = time.perf_counter()
        with trace_ctx(trace_dir), profile_ctx(profile_dir), \
                span("fit", route=entry.name, n_train=M,
                     streaming=streaming):
            with span(f"route.{entry.name}", engine=self.cfg.engine):
                out = entry.fit(self.problem, x, y, key, cfg=self.cfg,
                                mesh=self.mesh, data_axis=self.data_axis,
                                auto=auto, compile_kw=dict(self.compile_kw),
                                fit_kw=fit_kw)
            with span("fit.block_until_ready"):
                jax.block_until_ready(
                    out.model.w if out.model.w is not None
                    else out.model.coef)
        wall = time.perf_counter() - t0
        report = FitReport(
            route=entry.name, engine=out.engine, algorithm=entry.algorithm,
            n_train=M, n_sv=out.model.n_sv,
            compression=out.model.compression, wall_clock=wall,
            passes=out.passes, kkt=out.kkt, eta=out.eta,
            history=out.history, gap=out.model.gap, raw=out.raw)
        if tracker is not None:
            final = out.passes[0] if entry.name == "dsvrg" \
                else len(out.passes)
            tracker.log_metrics(final, {
                "route": entry.name, "engine": out.engine, "fit_done": True,
                "n_train": M, "n_sv": out.model.n_sv, "kkt": out.kkt,
                "wall_clock": wall,
                "rows_per_s": M / max(wall, 1e-9)})
        self.model_, self.report_ = out.model, report
        return out.model, report

    def _resume_manager(self, route: str, resume, x: Array, y: Array,
                        key: jax.Array, faults, streaming: bool = False):
        """Build the route's resume manager, fingerprinting THIS fit's
        (kernel, params, cfg, data, key) so a stale directory is rejected
        instead of splicing foreign duals into the solve. A streaming fit
        fingerprints the *source* (``source.fingerprint()``) instead of
        summing data nobody wants resident."""
        from repro.distributed import resume as resume_mod
        rc = resume_mod.ResumeConfig.of(resume)
        if streaming:
            prov = resume_mod.provenance_source(self.problem.kernel,
                                                self.problem.params,
                                                self.cfg, x, key)
        else:
            prov = resume_mod.provenance(self.problem.kernel,
                                         self.problem.params, self.cfg,
                                         x, y, key)
        cls = (resume_mod.DsvrgResumeManager if route == "dsvrg"
               else resume_mod.CascadeResumeManager)
        return cls(rc, prov, faults=faults)

    # -- scoring ------------------------------------------------------------

    def _fitted(self) -> serve_model.FittedODM:
        if self.model_ is None:
            raise ValueError(
                "this ODMEstimator is not fitted — call fit(x, y) first "
                "(or load() a saved artifact)")
        return self.model_

    def decision_function(self, x: Array, **kw) -> Array:
        """f(x) (T,) through the served scoring path."""
        return self._fitted().decision_function(x, **kw)

    def predict(self, x: Array, **kw) -> Array:
        """sign(f(x)) in {-1, +1}."""
        return self._fitted().predict(x, **kw)

    def score(self, x: Array, y: Array) -> float:
        """Accuracy of :meth:`predict` against ±1 labels."""
        return float(odm_mod.accuracy(y, self.predict(x)))

    # -- persistence --------------------------------------------------------

    def save(self, directory: str) -> str:
        """Persist the fitted artifact (atomic versioned checkpoint)."""
        return self._fitted().save(directory)

    @classmethod
    def load(cls, directory: str, *,
             problem: ProblemSpec | None = None) -> "ODMEstimator":
        """Restore an estimator that scores immediately (no refit).

        The artifact stores the kernel spec but not the training
        hyperparameters; pass ``problem`` to set them for a later refit,
        otherwise defaults are assumed.
        """
        model = serve_model.load_model(directory)
        est = cls(problem if problem is not None
                  else ProblemSpec(kernel=model.spec))
        est.model_ = model
        return est
