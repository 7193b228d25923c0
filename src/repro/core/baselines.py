"""Baseline scalable QP solvers the paper compares against (Section 4).

All baselines train the *same* ODM dual (so accuracy differences reflect
the partition/merge strategy, exactly the paper's experimental design):

* **Ca-ODM** — Cascade (Graf et al. 2004): binary-tree merge in which each
  node solves its local ODM and forwards only its "support" instances
  (ODM's complementary slackness: duals are nonzero iff the margin falls
  outside the [1-theta, 1+theta] band). Greedy data discarding makes it
  fast but lossy — the paper's Tables 2-3 show exactly that signature.

* **DiP-ODM** — DiP-SVM-style (Singh et al. 2017): k-means clusters in
  input space, each cluster dealt round-robin across partitions (first-
  order distribution preservation, but no RKHS-aware landmark/stratum
  construction), then the same hierarchical merge as SODM.

* **DC-ODM** — DC-SVM-style (Hsieh et al. 2014): each k-means *cluster is
  a partition* (maximally unlike the global distribution), concatenated
  duals warm-start the parent solve, same merge machinery.

* **ODM_svrg** — single-chain SVRG (Johnson & Zhang 2013) on the linear
  primal.

* **ODM_csvrg** — coreset SVRG (Tan et al. 2019): anchor full gradients
  evaluated on a k-center coreset instead of the full set.

Everything reuses repro.core.{dual_cd, sodm, partition, odm} so the only
variable is the strategy under test.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dual_cd, kernel_fns as kf
from repro.core import partition as part_mod
from repro.core import sodm as sodm_mod
from repro.core.odm import (ODMParams, minibatch_grad, primal_grad,
                            primal_objective)

Array = jax.Array


# ---------------------------------------------------------------------------
# Ca-ODM (Cascade)
# ---------------------------------------------------------------------------

class CascadeResult(NamedTuple):
    x_sv: Array
    y_sv: Array
    alpha: Array
    levels_run: int


def _top_support(x: Array, y: Array, alpha: Array, keep: int,
                 theta_band: float = 1e-8):
    """Keep the `keep` instances with largest dual magnitude |zeta - beta|.

    Static-shape-friendly (top_k); ODM support vectors are margin-band
    violators, which is exactly where |zeta-beta| > 0.
    """
    m = x.shape[0]
    zeta, beta = alpha[:m], alpha[m:]
    mag = jnp.abs(zeta - beta) + jnp.minimum(zeta, beta)   # ~ activity score
    _, idx = jax.lax.top_k(mag, keep)
    return x[idx], y[idx], jnp.concatenate([zeta[idx], beta[idx]])


def _cascade_solve(spec: kf.KernelSpec, x: Array, y: Array,
                   params: ODMParams, levels: int, key: jax.Array,
                   tol: float = 1e-4, max_sweeps: int = 100,
                   perm: Array | None = None) -> CascadeResult:
    """Binary cascade: 2^levels leaves; each merge keeps half the instances
    (the classic cascade funnel), solving on survivors only."""
    M = x.shape[0]
    K = 2 ** levels
    if M % K != 0:
        raise ValueError(f"2^levels={K} must divide M={M}")
    if perm is None:
        perm = part_mod.random_partitions(M, K, key)
    xp, yp = x[perm], y[perm]
    m = M // K
    xs = xp.reshape(K, m, -1)
    ys = yp.reshape(K, m)
    alphas = jnp.zeros((K, 2 * m), x.dtype)

    def make_solve_level(m):
        def solve_level(xs, ys, alphas):
            def one(xk, yk, ak):
                Q = kf.signed_gram(spec, xk, yk)
                res = dual_cd.solve(Q, params, mscale=float(m), alpha0=ak,
                                    tol=tol, max_sweeps=max_sweeps)
                return res.alpha
            return jax.vmap(one)(xs, ys, alphas)
        return jax.jit(solve_level)

    lvl = 0
    while True:
        alphas = make_solve_level(m)(xs, ys, alphas)
        lvl += 1
        if xs.shape[0] == 1:
            break
        # funnel: each node keeps its top m//2 "support" instances, then
        # pairs merge back to (2 * (m//2))-sized problems (handles odd m).
        keep = m // 2
        xk, yk, ak = jax.vmap(
            lambda a, b, c: _top_support(a, b, c, keep))(xs, ys, alphas)
        Kn = xs.shape[0] // 2
        m = 2 * keep
        xs = xk.reshape(Kn, m, -1)
        ys = yk.reshape(Kn, m)
        grouped = ak.reshape(Kn, 2, 2 * keep)
        alphas = jax.vmap(sodm_mod.merge_alphas)(grouped)
    return CascadeResult(x_sv=xs[0], y_sv=ys[0], alpha=alphas[0],
                         levels_run=lvl)


def _cascade_solve_stream(spec: kf.KernelSpec, source, params: ODMParams,
                          levels: int, key: jax.Array | None = None,
                          tol: float = 1e-4, max_sweeps: int = 100, *,
                          faults=None, tracker=None, resume=None,
                          depth: int = 2, executor=None, metrics=None,
                          accountant=None) -> CascadeResult:
    """Out-of-core cascade: level-0 partitions train as shards arrive.

    The dense solver loads all M rows, deals them into 2^levels leaves
    and sweeps the funnel level by level. This driver instead runs the
    cascade as an online binary tournament: each arriving leaf (one
    ``M / 2^levels``-row slab of the stream, cut on global row indices
    by ``iter_slabs``) is solved immediately, and whenever two
    same-level survivors sit on top of the merge stack they funnel the
    instant both exist — keep the top half of each
    (:func:`_top_support`), concatenate, warm-start from the merged
    duals (:func:`repro.core.sodm.merge_alphas`) and re-solve. At most
    ``levels + 1`` partially-merged nodes are ever resident, so host
    memory is O(leaf_rows · levels) whatever M is.

    With the dense solver given ``perm = arange(M)`` the tournament
    pairs exactly the same instances into exactly the same nodes; the
    results differ only by vmap-vs-single solve numerics (the parity
    tests pin ≤ 1e-5). Leaves stream in stream order — ``key`` is
    accepted for signature parity and unused.

    Instrumentation: the ``cascade.shard`` fault site fires per leaf
    (``data.prefetch`` fires underneath, inside the loader), a
    ``cascade.shard`` span wraps each leaf's solve+merge work, the
    tracker logs per-leaf throughput, and ``resume`` (a
    :class:`~repro.distributed.resume.CascadeResumeManager`) checkpoints
    the merge stack after each leaf — a restart re-enters the stream at
    the first unprocessed leaf without re-reading completed shards.
    """
    import time as _time

    from repro.data.streaming import loader as stream_loader
    from repro.observe.spans import span as _span

    M = int(source.n_rows)
    K = 2 ** levels
    if M % K != 0:
        raise ValueError(f"2^levels={K} must divide M={M}")
    del key
    m0 = M // K
    if metrics is None and tracker is not None:
        from repro.observe import MetricsRegistry
        metrics = MetricsRegistry()

    solvers: dict[int, object] = {}

    def solve_node(xn, yn, a0):
        m = int(xn.shape[0])
        if m not in solvers:
            def fn(xn, yn, a0, m=m):
                Q = kf.signed_gram(spec, xn, yn)
                return dual_cd.solve(Q, params, mscale=float(m), alpha0=a0,
                                     tol=tol, max_sweeps=max_sweeps).alpha
            solvers[m] = jax.jit(fn)
        return solvers[m](xn, yn, a0)

    # merge stack: (tier, x (m, d), y (m,), alpha (2m,)) — tier t holds
    # the solved merge of 2^t consecutive leaves
    stack: list[tuple[int, Array, Array, Array]] = []
    start_leaf = 0
    if resume is not None:
        restored = resume.restore_stream()
        if restored is not None:
            start_leaf = restored.leaf
            stack = [(t, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(al))
                     for t, xs, ys, al in restored.stack]

    def funnel():
        while len(stack) >= 2 and stack[-1][0] == stack[-2][0]:
            tier, xb, yb, ab = stack.pop()
            _, xa, ya, aa = stack.pop()
            keep = int(xa.shape[0]) // 2
            xa, ya, aa = _top_support(xa, ya, aa, keep)
            xb, yb, ab = _top_support(xb, yb, ab, keep)
            xm = jnp.concatenate([xa, xb])
            ym = jnp.concatenate([ya, yb])
            am = sodm_mod.merge_alphas(jnp.stack([aa, ab]))
            stack.append((tier + 1, xm, ym, solve_node(xm, ym, am)))

    slabs = stream_loader.iter_slabs(
        source, m0, start_row=start_leaf * m0, depth=depth,
        executor=executor, metrics=metrics, faults=faults,
        accountant=accountant)
    for slab in slabs:
        leaf = slab.start // m0
        if faults is not None:
            faults.site("cascade.shard", shard=leaf)
        t0 = _time.perf_counter()
        with _span("cascade.shard", shard=leaf, rows=m0):
            xl = jnp.asarray(slab.x)
            yl = jnp.asarray(slab.y)
            al = solve_node(xl, yl, jnp.zeros(2 * m0, xl.dtype))
            stack.append((0, xl, yl, al))
            funnel()
        if tracker is not None:
            jax.block_until_ready(stack[-1][3])
            wall = _time.perf_counter() - t0
            tracker.log_metrics(leaf + 1, {
                "route": "cascade", "leaf": leaf, "rows": m0,
                "wall_s": wall, "rows_per_s": m0 / max(wall, 1e-9)})
        if resume is not None:
            resume.save_stream(leaf=leaf + 1, stack=stack)
    if len(stack) != 1:               # K is a power of two: cannot happen
        raise RuntimeError(f"merge stack did not collapse: {len(stack)}")
    if metrics is not None and tracker is not None:
        metrics.drain(tracker, step=K)
    _, x_sv, y_sv, alpha = stack[0]
    return CascadeResult(x_sv=x_sv, y_sv=y_sv, alpha=alpha,
                         levels_run=levels + 1)


def cascade_predict(spec: kf.KernelSpec, res: CascadeResult,
                    x_test: Array) -> Array:
    """Served prediction for the cascade survivor set: compiled FittedODM
    (near-zero duals pruned, linear collapsed to w) through the tiled
    scorer — the dense (T, M) test Gram of the seed path is gone."""
    from repro.serve import model as serve_model
    return serve_model.from_cascade(spec, res).predict(x_test)


# ---------------------------------------------------------------------------
# DiP-ODM / DC-ODM — SODM machinery with rival partition strategies
# ---------------------------------------------------------------------------

def _dip_solve(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
               cfg: sodm_mod.SODMConfig,
               key: jax.Array) -> sodm_mod.SODMResult:
    """DiP: k-means clusters dealt round-robin across partitions. Reuses
    the stratified sampler with *k-means clusters as the strata*
    — the structural difference from SODM is the stratum construction
    (input-space centroids vs RKHS det-max landmarks)."""
    M = x.shape[0]
    K0 = cfg.p ** cfg.levels
    ck, pk = jax.random.split(key)
    # k-means strata
    perm_c = part_mod.cluster_partitions(spec, x, cfg.n_landmarks, ck)
    # recover cluster ids from the sorted permutation layout
    stratum = jnp.zeros(M, jnp.int32).at[perm_c].set(
        jnp.arange(M, dtype=jnp.int32) // (M // cfg.n_landmarks))
    perm = part_mod.stratified_partitions(stratum, K0, pk)
    xp, yp = x[perm], y[perm]
    res = sodm_mod._solve(
        spec, xp, yp, params,
        dataclasses.replace(cfg, partition_strategy="identity"), pk)
    # compose permutations (solve() used identity internally)
    return sodm_mod.SODMResult(alpha=res.alpha, perm=perm[res.perm],
                               levels_run=res.levels_run,
                               sweeps_per_level=res.sweeps_per_level,
                               kkt=res.kkt)


def _dc_solve(spec: kf.KernelSpec, x: Array, y: Array, params: ODMParams,
              cfg: sodm_mod.SODMConfig,
              key: jax.Array) -> sodm_mod.SODMResult:
    """DC: clusters *are* partitions (cluster_partitions layout)."""
    return sodm_mod._solve(
        spec, x, y, params,
        dataclasses.replace(cfg, partition_strategy="cluster"), key)


# ---------------------------------------------------------------------------
# gradient-based baselines (linear kernel)
# ---------------------------------------------------------------------------

class GradResult(NamedTuple):
    w: Array
    history: Array


def _svrg_solve(x: Array, y: Array, params: ODMParams, epochs: int,
                eta: float, key: jax.Array, batch: int = 1) -> GradResult:
    """Plain single-machine SVRG (Johnson & Zhang 2013)."""
    M, d = x.shape
    steps = M // batch

    @jax.jit
    def epoch(w, key):
        anchor = w
        h = primal_grad(anchor, x, y, params)
        idx = jax.random.permutation(key, M)[:steps * batch].reshape(steps, batch)

        def inner(w, ib):
            xb, yb = x[ib], y[ib]
            g_w = minibatch_grad(w, xb, yb, params, M)
            g_a = minibatch_grad(anchor, xb, yb, params, M)
            return w - eta * (g_w - g_a + h), None

        w, _ = jax.lax.scan(inner, w, idx)
        return w, primal_objective(w, x, y, params)

    w = jnp.zeros(d, x.dtype)
    hist = []
    for e in range(epochs):
        w, obj = epoch(w, jax.random.fold_in(key, e))
        hist.append(obj)
    return GradResult(w=w, history=jnp.stack(hist))


def kcenter_coreset(x: Array, n: int) -> Array:
    """Greedy k-center (farthest point) coreset indices."""
    M = x.shape[0]

    def body(s, carry):
        mind2, picks = carry
        i = jnp.where(s == 0, 0, jnp.argmax(mind2))
        picks = picks.at[s].set(i)
        xi = jax.lax.dynamic_slice(x, (i, 0), (1, x.shape[1]))
        d2 = jnp.sum((x - xi) ** 2, axis=1)
        return jnp.minimum(mind2, d2), picks

    mind2 = jnp.full((M,), jnp.inf, x.dtype)
    picks = jnp.zeros((n,), jnp.int32)
    _, picks = jax.lax.fori_loop(0, n, body, (mind2, picks))
    return picks


def _csvrg_solve(x: Array, y: Array, params: ODMParams, epochs: int,
                 eta: float, key: jax.Array, coreset_frac: float = 0.1,
                 batch: int = 1) -> GradResult:
    """Coreset-SVRG (Tan et al. 2019): anchor gradient on a k-center
    coreset."""
    M, d = x.shape
    n_core = max(1, int(M * coreset_frac))
    core = kcenter_coreset(x, n_core)
    xc, yc = x[core], y[core]
    steps = M // batch

    @jax.jit
    def epoch(w, key):
        anchor = w
        h = primal_grad(anchor, xc, yc, params)      # coreset anchor (cheap)
        idx = jax.random.permutation(key, M)[:steps * batch].reshape(steps, batch)

        def inner(w, ib):
            xb, yb = x[ib], y[ib]
            g_w = minibatch_grad(w, xb, yb, params, M)
            g_a = minibatch_grad(anchor, xb, yb, params, M)
            return w - eta * (g_w - g_a + h), None

        w, _ = jax.lax.scan(inner, w, idx)
        return w, primal_objective(w, x, y, params)

    w = jnp.zeros(d, x.dtype)
    hist = []
    for e in range(epochs):
        w, obj = epoch(w, jax.random.fold_in(key, e))
        hist.append(obj)
    return GradResult(w=w, history=jnp.stack(hist))
