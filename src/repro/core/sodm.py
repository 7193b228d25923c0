"""SODM Algorithm 1 — hierarchical partitioned ODM solve with warm starts.

Level l has K_l = p^l partitions of size m_l = M / K_l. Each partition's
local ODM (Eqn. 4 block) is solved by dual coordinate descent; when p
sibling partitions merge, their dual vectors are concatenated as the warm
start of the parent solve (Algorithm 1 line 12). Theorem 1 bounds the gap
between the block-diagonal approximation and the global dual, so the warm
start is already near-optimal and the parent solve converges in a few
sweeps.

Layout note: each local alpha is [zeta_k; beta_k] (2 m_l,). The parent's
alpha is [zeta_all; beta_all] (2 p m_l,), so "concatenation" interleaves:
parent_zeta = concat(zeta_children), parent_beta = concat(beta_children).
``merge_alphas`` implements exactly that.

Scale note: the local dual's diagonal regularizer is m_l·c (Eqn. 4), so
dual magnitudes shrink as partitions grow — at a merge the children's
duals were solved at scale m_l but the parent solves at scale p·m_l, and a
plain concatenation can be up to ~p× too large (its KKT residual is then
*worse* than a cold start's). Every solver engine therefore opens a level
solve with an exact line search along the warm-start ray (the dual
objective is quadratic in t, closed form — see
:func:`repro.core.odm.warm_start_scale`), which lands within a few KKT
digits of the parent optimum in both the regularizer-dominant (t ≈ 1/p)
and the Q-dominant (t ≈ 1) regime and is what makes Algorithm 1's warm
starts actually cut solve passes.

Two execution layouts:

* :func:`solve` — single-process: all partitions of a level advance
  together (levels are a Python loop; shapes are static per level so each
  level compiles once and is reused across calls with the same sizes).

* :func:`solve_sharded` — SPMD: ``shard_map`` over the mesh ``data`` axis.
  While K_l >= n_dev each device sweeps its own slab of partitions with
  **zero** cross-device traffic (the paper's "parallel training" phase);
  when a merge would span devices we all-gather X/y/alpha inside the merge
  group (axis-index arithmetic) — this is the Spark shuffle of the paper
  mapped onto ICI collectives. Once K_l < n_dev the residual levels run
  replicated (at that point the problem is a single in-memory QP anyway).

Solver engines
--------------

HOW each level's K local ODM duals are solved is orthogonal to WHERE they
run, so it is pluggable: ``SODMConfig.engine`` selects a
:class:`repro.core.engines.LocalSolver`:

* ``"scalar"`` (default) — exact Gauss-Seidel dual CD per partition, the
  paper-faithful reference. Latency-bound on accelerators.
* ``"block"``  — pure-jnp block-Gauss-Seidel (exact CD inside VMEM-sized
  tiles, Jacobi across tiles). The XLA oracle of the Pallas path.
* ``"pallas"`` — the greedy block-CD *fused* Pallas pass kernel: one
  ``pallas_call`` per pass runs the whole level's tile sweeps AND the
  cross-tile Gram matvec (no separate per-pass matmul), warm starts
  included; tiles early-exit their sweep at in-tile KKT <= tol (adaptive
  steps_per_pass). Partitions larger than ``SODMConfig.gram_threshold``
  rebuild Gram tiles on the fly from the raw features for every kernel
  family (rbf / laplacian / poly / linear — ``repro.kernels.gram``), so
  per-level memory stays O(m·B) instead of O(m²).
* ``"dsvrg"`` — the paper's linear-kernel path (Algorithm 2): the WHOLE
  problem routes to the communication-efficient primal SVRG solver
  (``repro.core.dsvrg``) instead of the hierarchical dual level loop, and
  the dual alpha is recovered from the primal solution via
  ``odm.alpha_from_w`` so predict/baselines work unchanged. Also selected
  AUTOMATICALLY — only when ``engine`` is left unset (None); an explicit
  scalar/block/pallas choice is always honored — for linear-kernel
  problems with M >= ``SODMConfig.dsvrg_threshold`` (the paper's "when
  linear kernel is applied" dispatch, now owned by
  ``repro.api.registry.resolve_auto``); ``SODMConfig.dsvrg`` carries the
  solver's own epochs/batch/schedule knobs.

``engine=None`` (the default) otherwise behaves exactly like
``"scalar"``.

All level engines honor Algorithm 1's warm starts (line 12) and report 0
sweeps/passes for an already-converged start (line 5's early stop).

Both layouts checkpoint per level through ``level_callback`` for fault
tolerance (see repro.distributed.checkpoint).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import deprecation as _dep
from repro.core import dsvrg as dsvrg_mod
from repro.core import engines, kernel_fns as kf
from repro.core import odm as odm_mod
from repro.core import partition as part_mod
from repro.core.odm import ODMParams
from repro.observe.spans import span as _span

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SODMConfig:
    """Hyperparameters of the SODM solve."""

    p: int = 2                 # merge factor (partitions merged per level)
    levels: int = 3            # L: start with p^L partitions
    n_landmarks: int = 8       # S strata
    tol: float = 1e-4          # per-solve KKT tolerance
    max_sweeps: int = 100      # CD sweep / outer-pass cap per local solve
    early_stop: bool = True    # Algorithm 1 line 5-6
    partition_strategy: str = "stratified"   # stratified | random | cluster
    engine: str | None = None  # None (auto) | scalar | block | pallas |
    #                            dsvrg. None runs the scalar level loop
    #                            EXCEPT for linear-kernel problems with
    #                            M >= dsvrg_threshold, which auto-route to
    #                            dsvrg; an explicitly named engine (scalar
    #                            included) is always honored (module docs)
    block: int = 256           # VMEM tile size of the block/pallas engines
    gram_threshold: int = 4096  # pallas: partitions above this rebuild
    #                             Gram tiles on the fly (repro.kernels.gram,
    #                             O(m·B) memory, all kernel families)
    #                             instead of materializing the O(m²) Q
    adaptive: bool = True      # pallas: tiles early-exit their greedy
    #                            sweep at in-tile KKT <= 0.01*tol (never
    #                            changes the outer exact-KKT convergence
    #                            check)
    dsvrg: dsvrg_mod.DSVRGConfig = dsvrg_mod.DSVRGConfig(epochs=10, batch=64)
    #                            solver knobs of the linear-kernel DSVRG
    #                            route (engine="dsvrg" or auto-dispatch);
    #                            n_partitions is clamped to divide M
    dsvrg_threshold: int = 200_000  # linear-kernel problems at/above this
    #                            many instances auto-route to the DSVRG
    #                            engine (the paper's "when linear kernel
    #                            is applied" dispatch)


class SODMResult(NamedTuple):
    alpha: Array             # (2M,) global-layout dual solution
    perm: Array              # (M,) partition permutation applied to the data
    levels_run: int
    sweeps_per_level: list   # python list of int sweep counts (max over partitions)
    kkt: Array               # final global KKT residual (if computed) or per-level


def merge_alphas(alphas: Array) -> Array:
    """(K, 2m) per-partition [zeta;beta] -> (2*K*m,) global [zeta_all;beta_all]."""
    K, two_m = alphas.shape
    m = two_m // 2
    zetas = alphas[:, :m].reshape(-1)
    betas = alphas[:, m:].reshape(-1)
    return jnp.concatenate([zetas, betas])


def split_to_partitions(alpha: Array, K: int) -> Array:
    """Inverse of merge_alphas: (2M,) -> (K, 2m)."""
    M = alpha.shape[0] // 2
    m = M // K
    zetas = alpha[:M].reshape(K, m)
    betas = alpha[M:].reshape(K, m)
    return jnp.concatenate([zetas, betas], axis=1)


def _solve_dsvrg(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
                 cfg: SODMConfig, key: jax.Array,
                 mesh: jax.sharding.Mesh | None = None,
                 data_axis: str = "data", auto: bool = False, *,
                 faults=None, tracker=None, resume=None,
                 ) -> tuple[SODMResult, dsvrg_mod.DSVRGResult]:
    """Whole-problem linear-kernel route (the registry's dsvrg entry).

    Solves the primal with DSVRG (Algorithm 2) and recovers the dual via
    ``odm.alpha_from_w`` so the result plugs into every alpha consumer;
    the native ``DSVRGResult`` is returned alongside so the unified API
    can report the objective history / eta and compile the artifact from
    the primal ``w`` directly. ``levels_run`` is 1 (a single
    whole-problem solve),
    ``sweeps_per_level`` reports the epoch count, and ``kkt`` is the
    primal gradient infinity norm (the natural stationarity residual of
    the primal path). The outer ``partition_strategy``/``n_landmarks``
    carry over when DSVRG supports the strategy (stratified/random;
    cluster/identity keep ``cfg.dsvrg``'s own setting). The solve is
    epoch-budgeted (``cfg.dsvrg.epochs``) — ``tol``/``max_sweeps`` are
    level-loop knobs and do not apply here; check the returned ``kkt`` if
    a stationarity guarantee is needed. An AUTO-dispatched solve on a
    mesh (``auto=True``) upgrades the default serial schedule to
    ``"parallel"``: the serial chain is replicated compute over an
    all-gathered slab — the right validation tool, but exactly wrong for
    the big-data regime that triggers the auto route. An explicit
    ``engine="dsvrg"`` keeps whatever ``cfg.dsvrg`` says.
    """
    from repro.api import registry
    M = x.shape[0]
    n_dev = mesh.shape[data_axis] if mesh is not None else 1
    K = registry.dsvrg_partition_count(M, cfg.dsvrg.n_partitions, n_dev)
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
    return SODMResult(alpha=alpha, perm=res.perm, levels_run=1,
                      sweeps_per_level=[dcfg.epochs], kkt=kkt), res


def _partition(spec: kf.KernelSpec, x: Array, cfg: SODMConfig, K0: int,
               key: jax.Array) -> Array:
    """The level-0 permutation by ``cfg.partition_strategy``, for both
    layouts: stratified, random, cluster or identity (the caller already
    laid the data out); any other name raises."""
    with _span("sodm.partition", strategy=cfg.partition_strategy, K=K0):
        if cfg.partition_strategy == "stratified":
            return part_mod.make_plan(spec, x, cfg.n_landmarks, K0, key).perm
        if cfg.partition_strategy == "random":
            return part_mod.random_partitions(x.shape[0], K0, key)
        if cfg.partition_strategy == "cluster":
            return part_mod.cluster_partitions(spec, x, K0, key)
        if cfg.partition_strategy == "identity":
            return jnp.arange(x.shape[0])
        raise ValueError(cfg.partition_strategy)


def _level_loop(run_level, x: Array, y: Array, perm: Array, cfg: SODMConfig,
                *, faults=None, tracker=None, resume=None,
                level_callback: Callable[[int, Array], None] | None = None,
                n_dev: int = 0) -> SODMResult:
    """The Algorithm-1 level loop, shared by the single-process and SPMD
    drivers (``run_level(xs, ys, alphas, K) -> (alphas, sweeps, kkts)`` is
    the only thing that differs between them; ``n_dev`` is the SPMD
    driver's data-axis size, 0 for the single-process one).

    Each ``cascade.level`` span carries the level's ``layout``
    (:func:`mesh_layout`, or ``"single"``), ``n_dev`` and
    ``passes_by_device``: the passes each device ran, the largest of its
    own partitions' ``sweeps`` (a sharded level's devices stop unevenly;
    a replicated level repeats one count). A replicated level's passes
    times ``n_dev - 1`` bump ``sodm.replicated_passes``. Where the engine
    returns kernel counters after the passes (``engines.split_sweeps``:
    the pallas engine's ``tile_steps`` and ``step_slots``), the span
    carries each summed over the level's partitions, one device's work on
    a replicated level.

    Instrumentation seams, all default-off:

    * ``faults`` — a :class:`repro.distributed.faults.FaultPlan`; the
      ``"cascade.level"`` site fires BEFORE each level solve, so a kill at
      level k leaves level k+1's checkpoint as the last committed state
      and a resume restarts exactly the killed solve from the merged
      level-(k+1) duals (Algorithm 1's warm start, recovered from disk).
    * ``tracker`` — per-level KKT / sweeps / SV-count / throughput via
      ``log_metrics(levels_solved, {...})`` (repro.observe).
    * ``resume`` — a :class:`repro.distributed.resume
      .CascadeResumeManager`; every solved level is checkpointed, and a
      non-empty resume directory re-enters the loop at the first unsolved
      level (the restored level is treated as already solved: straight to
      the convergence check and merge). Level solves are deterministic
      pure functions of ``(xs, ys, alphas)`` and the checkpoint round
      trip is bitwise exact, so the resumed result is bit-identical to an
      uninterrupted run's.
    """
    restored = resume.restore() if resume is not None else None
    M = x.shape[0]
    n = n_dev or 1
    if restored is not None:
        level, K, m = restored.level, restored.K, restored.m
        alphas, perm = restored.alphas, restored.perm
        sweeps_per_level = list(restored.sweeps_per_level)
        kkt = restored.kkt
        pending = False          # the restored level is already solved
    else:
        K = cfg.p ** cfg.levels
        m = M // K
        alphas = jnp.zeros((K, 2 * m), x.dtype)
        sweeps_per_level = []
        kkt = jnp.array(jnp.inf, x.dtype)
        level = cfg.levels
        pending = True
    xp, yp = x[perm], y[perm]

    while True:
        if pending:
            if faults is not None:
                faults.site("cascade.level", level=level, K=K)
            _LEVEL_SOLVE_COUNTER.bump((level, K))
            t0 = time.perf_counter()
            layout = mesh_layout(K, n_dev) if n_dev else "single"
            with _span("cascade.level", level=level, K=K, m=m) as sp:
                xs = xp.reshape(K, m, -1)
                ys = yp.reshape(K, m)
                alphas, sweeps, kkts = run_level(xs, ys, alphas, K)
                passes, counts = engines.split_sweeps(sweeps)
                by_dev = passes_by_device(passes, layout, n)
                sweeps_per_level.append(max(by_dev))
                kkt = jnp.max(kkts)
                sp.set(passes=sweeps_per_level[-1], layout=layout, n_dev=n,
                       passes_by_device=by_dev, **counts)
            if layout == "replicated":
                for _ in range(sweeps_per_level[-1] * (n - 1)):
                    _REPLICATED_PASS_COUNTER.bump((level, K))
            if tracker is not None:
                jax.block_until_ready(alphas)
                wall = time.perf_counter() - t0
                sv = int(jnp.sum(jnp.abs(alphas[:, :m] - alphas[:, m:]) > 0))
                tracker.log_metrics(len(sweeps_per_level), {
                    "route": "sodm", "level": level, "K": K, "m": m,
                    "sweeps": sweeps_per_level[-1], "kkt": float(kkt),
                    "sv_count": sv, "wall_s": wall,
                    "rows_per_s": M / max(wall, 1e-9)})
            if resume is not None:
                resume.save_level(level=level, K=K, m=m, alphas=alphas,
                                  perm=perm,
                                  sweeps_per_level=sweeps_per_level,
                                  kkt=kkt)
            if level_callback is not None:
                level_callback(level, alphas)
        pending = True
        # Algorithm 1 line 5: if all local solves already satisfied the
        # warm start (0 sweeps => init was within tol), we are converged.
        converged = cfg.early_stop and sweeps_per_level \
            and sweeps_per_level[-1] == 0 and level < cfg.levels
        if K == 1 or level == 0 or converged:
            break
        # merge p siblings: (K, 2m) -> (K/p, 2pm), interleaving zeta/beta
        # (plain concatenation, Algorithm 1 line 12 — the engine rescales
        # the warm start to the parent's regularizer scale, see the
        # module's scale note)
        Kn = K // cfg.p
        with _span("cascade.merge", level=level - 1, K=Kn):
            grouped = alphas.reshape(Kn, cfg.p, 2 * m)
            alphas = jax.vmap(merge_alphas)(grouped)   # (Kn, 2 p m)
        K, m = Kn, m * cfg.p
        level -= 1

    alpha = merge_alphas(alphas) if alphas.ndim == 2 and alphas.shape[0] > 1 \
        else alphas.reshape(-1)
    return SODMResult(alpha=alpha, perm=perm,
                      levels_run=len(sweeps_per_level),
                      sweeps_per_level=sweeps_per_level, kkt=kkt)


def solve(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
          cfg: SODMConfig, key: jax.Array,
          level_callback: Callable[[int, Array], None] | None = None,
          ) -> SODMResult:
    """Single-process SODM (Algorithm 1) — legacy entry point; the
    supported front door is ``repro.api.ODMEstimator`` (this shim warns
    once and delegates unchanged). Linear-kernel problems may route to
    the DSVRG primal engine (Algorithm 2) per the registry's dispatch
    policy (``level_callback`` does not fire on that path: there are no
    levels)."""
    _dep.warn_once("repro.core.sodm.solve", "repro.api.ODMEstimator.fit")
    return _solve(spec, x, y, params, cfg, key, level_callback)


def _solve(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
           cfg: SODMConfig, key: jax.Array,
           level_callback: Callable[[int, Array], None] | None = None,
           *, faults=None, tracker=None, resume=None) -> SODMResult:
    M = x.shape[0]
    if engines.wants_dsvrg(cfg.engine, spec.name, M, cfg.dsvrg_threshold):
        return _solve_dsvrg(spec, x, y, params, cfg, key, faults=faults,
                            tracker=tracker, resume=resume)[0]
    K0 = cfg.p ** cfg.levels
    if M % K0 != 0:
        raise ValueError(f"p^L={K0} must divide M={M}")

    perm = _partition(spec, x, cfg, K0, key)
    solver = engines.make_local_solver(cfg.engine, block=cfg.block,
                                       gram_threshold=cfg.gram_threshold,
                                       adaptive=cfg.adaptive)
    solve_jit = jax.jit(solver,
                        static_argnames=("spec", "params", "tol", "max_sweeps"))

    def run_level(xs, ys, alphas, K):
        del K
        return solve_jit(xs, ys, alphas, spec=spec, params=params,
                         tol=cfg.tol, max_sweeps=cfg.max_sweeps)

    return _level_loop(run_level, x, y, perm, cfg, faults=faults,
                       tracker=tracker, resume=resume,
                       level_callback=level_callback)


# ---------------------------------------------------------------------------
# SPMD engine (shard_map over the mesh `data` axis)
# ---------------------------------------------------------------------------

def solve_sharded(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
                  cfg: SODMConfig, key: jax.Array, mesh: jax.sharding.Mesh,
                  data_axis: str = "data") -> SODMResult:
    """SODM with partitions sharded over ``mesh[data_axis]`` — legacy
    entry point; the supported front door is ``repro.api.ODMEstimator``
    with ``mesh=`` (this shim warns once and delegates unchanged).

    Preconditions: p^L partitions, n_dev = mesh.shape[data_axis], and
    p^L % n_dev == 0 (each device starts with an equal slab). Levels with
    K_l >= n_dev run with zero communication. Once K_l < n_dev the data
    no longer fills the axis; we gather everything and finish replicated —
    at that point the problem is a single in-memory QP anyway. Every level
    is solved exactly once (no re-solve at the sharded/replicated
    hand-off) and ``levels_run`` reports the true count.
    """
    _dep.warn_once("repro.core.sodm.solve_sharded",
                   "repro.api.ODMEstimator.fit")
    return _solve_sharded(spec, x, y, params, cfg, key, mesh,
                          data_axis=data_axis)


def mesh_layout(K: int, n_dev: int) -> str:
    """How the SPMD driver runs a level of ``K`` partitions on ``n_dev``
    devices: ``"sharded"`` while every device holds an equal slab of
    them, else ``"replicated"`` (every device solves the whole level)."""
    return "sharded" if n_dev > 1 and K % n_dev == 0 else "replicated"


def passes_by_device(sweeps: np.ndarray, layout: str,
                     n_dev: int) -> list[int]:
    """Passes each device ran at a level, from its per-partition
    ``sweeps`` (K,): the largest over the device's own slab of a sharded
    level, else the level's largest on every device."""
    if layout == "sharded":
        return [int(v) for v in sweeps.reshape(n_dev, -1).max(axis=1)]
    return [int(sweeps.max())] * n_dev


def mesh_level_solves(body, mesh: jax.sharding.Mesh, data_axis: str):
    """The two jitted SPMD forms of a level solve ``body(xs, ys, alphas)``.

    ``sharded``: the parallel phase — each device sweeps its own slab of
    the level's partitions with zero communication. ``replicated``: the
    tail (K < n_dev partitions left, a single in-memory QP by now) — every
    device solves the whole level on replicated inputs. Both are
    ``shard_map``s: a Pallas kernel cannot be partitioned automatically,
    so left to the partitioner the tail would not compile on TPU.
    """
    def on_mesh(spec):
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3,
            # per-partition outputs are either fully sharded or the same
            # solve on every device: nothing to check through the
            # while_loops
            check_vma=False))
    return on_mesh(P(data_axis)), on_mesh(P())


def _solve_sharded(spec: kf.KernelSpec, x: Array, y: Array,
                   params: ODMParams, cfg: SODMConfig, key: jax.Array,
                   mesh: jax.sharding.Mesh, data_axis: str = "data",
                   *, faults=None, tracker=None, resume=None) -> SODMResult:
    M = x.shape[0]
    if engines.wants_dsvrg(cfg.engine, spec.name, M, cfg.dsvrg_threshold):
        return _solve_dsvrg(spec, x, y, params, cfg, key, mesh=mesh,
                            data_axis=data_axis,
                            auto=cfg.engine != "dsvrg", faults=faults,
                            tracker=tracker, resume=resume)[0]
    K0 = cfg.p ** cfg.levels
    n_dev = mesh.shape[data_axis]
    if K0 % n_dev != 0:
        raise ValueError(f"p^L={K0} must be a multiple of data axis {n_dev}")

    perm = _partition(spec, x, cfg, K0, key)
    solver = engines.make_local_solver(cfg.engine, block=cfg.block,
                                       gram_threshold=cfg.gram_threshold,
                                       adaptive=cfg.adaptive)
    body = partial(solver, spec=spec, params=params, tol=cfg.tol,
                   max_sweeps=cfg.max_sweeps)
    sharded, replicated = mesh_level_solves(body, mesh, data_axis)

    def run_level(xs, ys, alphas, K):
        if mesh_layout(K, n_dev) == "sharded":
            return sharded(xs, ys, alphas)
        return replicated(xs, ys, alphas)

    return _level_loop(run_level, x, y, perm, cfg, faults=faults,
                       tracker=tracker, resume=resume, n_dev=n_dev)


# ---------------------------------------------------------------------------
# convenience: fit + predict in original index order
# ---------------------------------------------------------------------------

# compiled-model cache for the stateless predict() API. The seed-era
# predict re-gathered x_train[res.perm] / y_train[res.perm] — an O(M·d)
# permutation gather plus a fresh (T, M) Gram — on EVERY call; compiling
# the FittedODM once amortizes the gather and SV packing across calls.
# Entries hold WEAK references to their key arrays: a live weakref proves
# the id() key has not been recycled, and a dead one invalidates the
# entry without pinning the (potentially multi-GB) training set in memory
# for the cache's lifetime. FIFO-capped as a second bound.
_MODEL_CACHE: dict = {}
_MODEL_CACHE_CAP = 8

# the gather pin now lives in the invariant registry (one counter store
# for every subsystem); this name is the back-compat alias
from repro.analysis.invariants import counter as _inv_counter  # noqa: E402

_PERM_GATHER_COUNTER = _inv_counter("sodm.perm_gather")

# one bump per level solve actually run (restored levels do NOT bump);
# the resume.cascade_fewer_solves invariant reads deltas of this to prove
# a resumed fit re-runs only the not-yet-solved levels
_LEVEL_SOLVE_COUNTER = _inv_counter("sodm.level_solve")

# one bump per pass a replicated level repeats on a device beyond the
# first: the work a sharded tail would save
_REPLICATED_PASS_COUNTER = _inv_counter("sodm.replicated_passes")


def level_solve_count() -> int:
    """How many cascade level solves have run in this process — resumed
    fits skip restored levels, so the delta across a resume must be
    smaller than a cold restart's (``resume.cascade_fewer_solves`` in
    ``repro.analysis.invariants``)."""
    return _LEVEL_SOLVE_COUNTER.count


def perm_gather_count() -> int:
    """How many times predict/fit have gathered x_train[res.perm] — the
    per-call-gather pin (``routes.sodm.predict_gather_once`` in
    ``repro.analysis.invariants``) holds this at one per fitted model."""
    return _PERM_GATHER_COUNTER.count


def compile_model(spec: kf.KernelSpec, res: SODMResult, x_train: Array,
                  y_train: Array, **kw):
    """Compile an ``SODMResult`` into a served ``FittedODM`` (the ONE
    place the partition permutation is applied). ``kw`` forwards
    compression knobs (prune_tol / budget / target)."""
    from repro.serve import model as serve_model
    _PERM_GATHER_COUNTER.bump((id(res), x_train.shape))
    return serve_model.from_sodm(spec, res, x_train, y_train, **kw)


def _weakrefs(*arrays):
    import weakref
    try:
        return tuple(weakref.ref(a) for a in arrays)
    except TypeError:                  # non-weakref-able leaf: no liveness
        return None                    # proof => never cache-hit on it


def _cached_model(spec: kf.KernelSpec, res: SODMResult, x_train: Array,
                  y_train: Array):
    key = (id(res.alpha), id(res.perm), id(x_train), id(y_train), spec)
    hit = _MODEL_CACHE.get(key)
    if hit is not None:
        model, refs = hit
        if refs is not None and all(r() is not None for r in refs):
            return model
        del _MODEL_CACHE[key]          # an id was (or could be) recycled
    model = compile_model(spec, res, x_train, y_train)
    if len(_MODEL_CACHE) >= _MODEL_CACHE_CAP:
        _MODEL_CACHE.pop(next(iter(_MODEL_CACHE)))
    _MODEL_CACHE[key] = (model, _weakrefs(res.alpha, res.perm,
                                          x_train, y_train))
    return model


def fit(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
        cfg: SODMConfig, key: jax.Array):
    """Solve + compile in one step: returns ``(SODMResult, FittedODM)``.

    Legacy entry point — the supported training API is
    ``repro.api.ODMEstimator.fit``, which returns ``(FittedODM,
    FitReport)``. THIS shim's tuple shape ``(SODMResult, FittedODM)`` is
    frozen for back-compat (pinned by tests/test_api.py); it warns once
    and delegates unchanged. The artifact is the deployable model — the
    permutation gather and SV packing happen here exactly once, never
    again at predict time.
    """
    _dep.warn_once("repro.core.sodm.fit", "repro.api.ODMEstimator.fit")
    res = _solve(spec, x, y, params, cfg, key)
    return res, _cached_model(spec, res, x, y)


def predict(spec: kf.KernelSpec, res: SODMResult, x_train: Array,
            y_train: Array, x_test: Array) -> Array:
    """Served prediction through a cached compiled model: the permutation
    gather runs once per fitted model (pinned by ``perm_gather_count``),
    and scoring is the tiled matrix-free path — no per-call (T, M) Gram."""
    return _cached_model(spec, res, x_train, y_train).predict(x_test)
