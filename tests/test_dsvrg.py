"""DSVRG (Algorithm 2): faithful serial chain + parallel variant.

PR 3 battery on top of the convergence smoke tests:
  * regressions for the three silent-wrong-answer bugs (hardcoded sharded
    eta, dropped ragged-tail samples, host objective recompute),
  * sharded-vs-serial parity on a CPU mesh for both schedules,
  * fused-Pallas vs jnp inner-direction parity,
  * the trace-once pin of the epoch-scan drivers.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from repro import sharding
from repro.analysis import invariants as inv
from repro.api import ODMEstimator, ProblemSpec, registry
from repro.core import dsvrg, kernel_fns as kf, odm, sodm
from repro.kernels import ops


def _data(M=512, d=12, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.concatenate([jax.random.normal(k1, (M // 2, d)) + 0.7,
                         jax.random.normal(k2, (M // 2, d)) - 0.7])
    y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
    perm = jax.random.permutation(k3, M)
    return x[perm], y[perm]


PARAMS = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)


def _gd_ref(x, y, iters=400, eta=0.05):
    w = jnp.zeros(x.shape[1])
    for _ in range(iters):
        w = w - eta * odm.primal_grad(w, x, y, PARAMS)
    return odm.primal_objective(w, x, y, PARAMS)


def _mesh1():
    return sharding.make_mesh((1,), ("data",))


class TestDSVRG:
    def test_serial_converges_to_gd_objective(self):
        x, y = _data()
        ref = _gd_ref(x, y)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=8, eta=0.05, batch=8)
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        assert float(res.history[-1]) < float(ref) * 1.02

    def test_parallel_converges(self):
        x, y = _data()
        ref = _gd_ref(x, y)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=8, eta=0.05,
                                batch=8, schedule="parallel")
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        assert float(res.history[-1]) < float(ref) * 1.02

    def test_objective_monotone_late(self):
        """After warmup the epoch objective should be non-increasing."""
        x, y = _data()
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=8, eta=0.03, batch=8)
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(2))
        h = [float(v) for v in res.history]
        assert h[-1] <= h[2] + 1e-6

    def test_accuracy(self):
        x, y = _data()
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=6, eta=0.05, batch=8)
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(3))
        acc = float(odm.accuracy(y, jnp.sign(x @ res.w)))
        assert acc > 0.9

    def test_stratified_vs_random_partitions(self):
        """Both run; stratified should not be worse in objective."""
        x, y = _data()
        base = dict(n_partitions=8, epochs=5, eta=0.05, batch=8)
        r1 = dsvrg._solve(x, y, PARAMS,
                          dsvrg.DSVRGConfig(**base), jax.random.PRNGKey(4))
        r2 = dsvrg._solve(x, y, PARAMS,
                          dsvrg.DSVRGConfig(partition_strategy="random",
                                            **base), jax.random.PRNGKey(4))
        assert float(r1.history[-1]) <= float(r2.history[-1]) * 1.05

    def test_monotone_on_device_history_auto_eta(self):
        """The device-side history with the auto smoothness step is
        monotone non-increasing from the first epoch (the conservative
        0.5/L_hat step never overshoots on this convex objective)."""
        x, y = _data()
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=8, batch=8)
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(5))
        assert res.history.shape == (8,)
        h = [float(v) for v in res.history]
        assert all(b <= a + 1e-6 for a, b in zip(h, h[1:])), h
        assert float(res.eta) > 0.0


# ---------------------------------------------------------------------------
# PR 3 regressions: the three silent-wrong-answer bugs
# ---------------------------------------------------------------------------

class TestEtaRegression:
    """make_sharded_epoch used to fall back to a hardcoded eta=0.05 when
    cfg.eta <= 0 and no explicit eta was passed, ignoring auto_eta."""

    def test_sharded_epoch_uses_auto_eta(self):
        x, y = _data(M=128, d=5)
        mesh = _mesh1()
        # lam=4 pushes auto_eta well away from the old 0.05 constant
        params = odm.ODMParams(lam=4.0, theta=0.1, ups=0.5)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=1, batch=4)
        xs = x.reshape(8, 16, 5)
        ys = y.reshape(8, 16)
        w0 = jnp.zeros(5)
        eta_ref = dsvrg.auto_eta(x, params)
        assert abs(eta_ref - 0.05) > 1e-3   # else the regression can't bite

        w_auto, _ = dsvrg.make_sharded_epoch(mesh, params, cfg, 128)(
            w0, xs, ys)
        w_explicit, _ = dsvrg.make_sharded_epoch(
            mesh, params, cfg, 128, eta=eta_ref)(w0, xs, ys)
        w_old_bug, _ = dsvrg.make_sharded_epoch(
            mesh, params, cfg, 128, eta=0.05)(w0, xs, ys)
        assert jnp.allclose(w_auto, w_explicit, atol=1e-6)
        assert not jnp.allclose(w_auto, w_old_bug, atol=1e-6)

    def test_sharded_and_single_process_same_step_size(self):
        x, y = _data(M=128, d=5)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=2, batch=4)
        r1 = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(0))
        r2 = dsvrg._solve_sharded(x, y, PARAMS, cfg, jax.random.PRNGKey(0),
                                  _mesh1())
        assert jnp.allclose(r1.eta, r2.eta, rtol=1e-6)
        assert jnp.allclose(r1.eta, dsvrg.auto_eta(x, PARAMS), rtol=1e-5)


class TestTailRegression:
    """_epoch_serial/_epoch_parallel used to run m // batch steps and
    silently skip the last m % batch samples of every partition."""

    def _setup(self, m=13, batch=5, K=2, d=4, seed=7):
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 5)
        xs = jax.random.normal(ks[0], (K, m, d))
        ys = jnp.sign(jax.random.normal(ks[1], (K, m)))
        w = jax.random.normal(ks[2], (d,)) * 0.1
        anchor = jax.random.normal(ks[3], (d,)) * 0.1
        h = odm.primal_grad(anchor, xs.reshape(-1, d), ys.reshape(-1),
                            PARAMS)
        return xs, ys, w, anchor, h

    @staticmethod
    def _serial_ref(w, xs, ys, anchor, h, eta, batch, *, drop_tail):
        """Plain-python without-replacement chain; the oracle consumes the
        ragged tail as a final short batch (mean over its true size)."""
        K, m, d = xs.shape
        stop = (m // batch) * batch if drop_tail else m
        for k in range(K):
            for i in range(0, stop, batch):
                xb, yb = xs[k, i:i + batch], ys[k, i:i + batch]
                w = w - eta * odm.svrg_direction(w, anchor, h, xb, yb,
                                                 PARAMS)
        return w

    def test_serial_consumes_every_sample(self):
        xs, ys, w, anchor, h = self._setup()
        eta = 0.05
        xsb, ysb, wts = dsvrg._pad_batches(xs, ys, 5)
        got = dsvrg._epoch_serial(w, xsb, ysb, wts, anchor, h, eta, PARAMS,
                                  fused=False)
        ref = self._serial_ref(w, xs, ys, anchor, h, eta, 5, drop_tail=False)
        old = self._serial_ref(w, xs, ys, anchor, h, eta, 5, drop_tail=True)
        assert not jnp.allclose(ref, old, atol=1e-6)  # the tail must matter
        assert jnp.allclose(got, ref, atol=1e-5)

    def test_parallel_consumes_every_sample(self):
        xs, ys, w, anchor, h = self._setup()
        eta = 0.05
        xsb, ysb, wts = dsvrg._pad_batches(xs, ys, 5)
        got = dsvrg._epoch_parallel(w, xsb, ysb, wts, anchor, h, eta,
                                    PARAMS, fused=False)
        chains = [self._serial_ref(w, xs[k:k + 1], ys[k:k + 1], anchor, h,
                                   eta, 5, drop_tail=False)
                  for k in range(xs.shape[0])]
        ref = jnp.mean(jnp.stack(chains), axis=0)
        assert jnp.allclose(got, ref, atol=1e-5)

    def test_ragged_batch_matches_batch1_coverage(self):
        """batch ∤ m must consume the same sample set as batch=1: with a
        common anchor-only direction (w == anchor ⇒ direction == h) the
        two batch sizes take the same total step, whatever the slicing."""
        x, y = _data(M=104, d=4)          # m = 13 per partition, 13 % 5 != 0
        cfg5 = dsvrg.DSVRGConfig(n_partitions=8, epochs=1, batch=5, eta=1e-9)
        cfg1 = dsvrg.DSVRGConfig(n_partitions=8, epochs=1, batch=1, eta=1e-9)
        r5 = dsvrg._solve(x, y, PARAMS, cfg5, jax.random.PRNGKey(0))
        r1 = dsvrg._solve(x, y, PARAMS, cfg1, jax.random.PRNGKey(0))
        # at eta -> 0 the epoch is sum over steps of eta*(direction at w0);
        # equal coverage ⇔ equal first-order displacement. The old tail
        # drop loses 3/13 of every partition's anchor mass here.
        d5 = (r5.w) / 1e-9
        d1 = (r1.w) / 1e-9
        n_steps5 = 3 * 8    # ceil(13/5) per partition
        n_steps1 = 13 * 8
        assert jnp.allclose(d5 / n_steps5, d1 / n_steps1, rtol=1e-3)


class TestHistoryOnDevice:
    """solve_sharded used to discard the epoch fn's objective and
    recompute primal_objective over the full permuted data on host."""

    def test_sharded_history_is_global_objective(self):
        x, y = _data(M=128, d=5)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=3, batch=4)
        res = dsvrg._solve_sharded(x, y, PARAMS, cfg, jax.random.PRNGKey(0),
                                   _mesh1())
        xp, yp = x[res.perm], y[res.perm]
        host_obj = float(odm.primal_objective(res.w, xp, yp, PARAMS))
        assert abs(float(res.history[-1]) - host_obj) < 1e-5

    def test_histories_match_across_layouts(self):
        x, y = _data(M=128, d=5)
        for schedule in ("serial", "parallel"):
            cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=3, batch=4,
                                    schedule=schedule)
            r1 = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(0))
            r2 = dsvrg._solve_sharded(x, y, PARAMS, cfg,
                                      jax.random.PRNGKey(0), _mesh1())
            assert jnp.allclose(r1.history, r2.history, atol=1e-5), schedule


# ---------------------------------------------------------------------------
# parity: sharded vs serial, fused vs jnp
# ---------------------------------------------------------------------------

class TestParity:
    def test_sharded_matches_single_process_both_schedules(self):
        x, y = _data(M=128, d=5)
        for schedule in ("serial", "parallel"):
            # batch 3 ∤ m = 16 exercises the masked tail through the full
            # sharded driver as well
            cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=4, batch=3,
                                    schedule=schedule)
            r1 = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(4))
            r2 = dsvrg._solve_sharded(x, y, PARAMS, cfg,
                                      jax.random.PRNGKey(4), _mesh1())
            assert jnp.allclose(r1.w, r2.w, atol=1e-5), schedule
            assert jnp.allclose(r1.history, r2.history, atol=1e-5), schedule

    def test_fused_pallas_matches_jnp_direction(self):
        """ops.svrg_grad (interpret-mode Pallas) vs odm.svrg_direction."""
        key = jax.random.PRNGKey(0)
        for B, d, masked in ((16, 8, False), (13, 7, True), (260, 5, True)):
            ks = jax.random.split(jax.random.fold_in(key, B), 6)
            x = jax.random.normal(ks[0], (B, d))
            y = jnp.sign(jax.random.normal(ks[1], (B,)))
            w = jax.random.normal(ks[2], (d,))
            a = jax.random.normal(ks[3], (d,))
            h = jax.random.normal(ks[4], (d,))
            wt = None
            if masked:
                wt = (jax.random.uniform(ks[5], (B,)) > 0.3).astype(x.dtype)
            ref = odm.svrg_direction(w, a, h, x, y, PARAMS, wb=wt)
            fused = ops.svrg_grad(w, a, h, x, y, wt, lam=PARAMS.lam,
                                  theta=PARAMS.theta, ups=PARAMS.ups)
            assert float(jnp.max(jnp.abs(ref - fused))) <= 1e-5

    def test_fused_solve_matches_jnp_solve(self):
        x, y = _data(M=64, d=6)
        for schedule in ("serial", "parallel"):
            base = dict(n_partitions=4, epochs=2, batch=5, schedule=schedule)
            r0 = dsvrg._solve(x, y, PARAMS,
                              dsvrg.DSVRGConfig(fused=False, **base),
                              jax.random.PRNGKey(1))
            r1 = dsvrg._solve(x, y, PARAMS,
                              dsvrg.DSVRGConfig(fused=True, **base),
                              jax.random.PRNGKey(1))
            assert jnp.allclose(r0.w, r1.w, atol=1e-5), schedule
            assert jnp.allclose(r0.history, r1.history, atol=1e-5), schedule


# ---------------------------------------------------------------------------
# the dsvrg route (paper: "when linear kernel is applied")
# ---------------------------------------------------------------------------

def _fit(cfg, x, y, key, kernel="linear", mesh=None):
    """(artifact, report) of a fit through the front door."""
    problem = ProblemSpec(kernel=kf.KernelSpec(name=kernel), params=PARAMS)
    return ODMEstimator(problem, cfg=cfg, mesh=mesh).fit(x, y, key)


class TestEngineRoute:
    def test_engine_dsvrg_matches_dual_cd_accuracy(self):
        x, y = _data()
        spec = kf.KernelSpec(name="linear")
        cfg = sodm.SODMConfig(
            engine="dsvrg",
            dsvrg=dsvrg.DSVRGConfig(n_partitions=8, epochs=8, batch=16))
        model, rep = _fit(cfg, x, y, jax.random.PRNGKey(1))
        acc = float(odm.accuracy(y, model.predict(x)))
        ref = sodm._solve(spec, x, y, PARAMS,
                          sodm.SODMConfig(p=2, levels=3, tol=1e-5,
                                          max_sweeps=200),
                          jax.random.PRNGKey(1))
        acc_cd = float(odm.accuracy(y, sodm.predict(spec, ref, x, y, x)))
        assert abs(acc - acc_cd) <= 0.005
        assert rep.route == "dsvrg" and rep.passes == (8,)
        assert rep.raw.history.shape == (8,)

    def test_engine_dsvrg_requires_linear_kernel(self):
        x, y = _data(M=64, d=4)
        cfg = sodm.SODMConfig(engine="dsvrg")
        with pytest.raises(ValueError, match="linear"):
            _fit(cfg, x, y, jax.random.PRNGKey(0), kernel="rbf")

    def test_auto_dispatch_upgrades_unset_engine_only(self):
        """Auto-route fires only when the engine is left unset (None) —
        every explicitly named engine, scalar included, is honored."""
        def route(kernel, M, threshold, engine=None):
            return registry.resolve_auto(kernel, M, engine=engine,
                                         threshold=threshold).name
        assert route("linear", 10, 5) == "dsvrg"
        assert route("linear", 10, 50) == "sodm"
        assert route("rbf", 10, 5) == "sodm"
        for explicit in ("scalar", "block", "pallas"):
            assert route("linear", 10, 5, engine=explicit) == "sodm"
        # end-to-end: tiny threshold routes the unset engine (the DSVRG
        # route runs its epochs, the level loop runs levels+1 solves)
        x, y = _data(M=128, d=5)
        auto = sodm.SODMConfig(
            dsvrg_threshold=64,
            dsvrg=dsvrg.DSVRGConfig(n_partitions=8, epochs=4, batch=8))
        _, rep = _fit(auto, x, y, jax.random.PRNGKey(0))
        assert rep.route == "dsvrg" and rep.passes == (4,)
        pinned = sodm.SODMConfig(engine="scalar", p=2, levels=2,
                                 dsvrg_threshold=64)
        _, rep2 = _fit(pinned, x, y, jax.random.PRNGKey(0))
        assert rep2.route == "sodm"
        assert rep2.raw.levels_run == 3      # the level loop actually ran

    def test_auto_route_on_mesh_prefers_parallel_schedule(self):
        """An AUTO-dispatched sharded solve upgrades the default serial
        schedule to parallel (the serial chain replicates the whole slab
        on every device — wrong for the regime that triggers the route);
        an explicit engine="dsvrg" keeps the configured schedule."""
        x, y = _data(M=128, d=5)
        mesh = _mesh1()
        base = dsvrg.DSVRGConfig(n_partitions=8, epochs=2, batch=8)
        assert base.schedule == "serial"
        traces = inv.counter("dsvrg.epoch_trace")

        def last_routed_cfg(n_before):
            assert traces.count > n_before          # fresh trace
            return traces.events[-1][1]

        n0 = traces.count
        _fit(sodm.SODMConfig(dsvrg_threshold=64, dsvrg=base), x, y,
             jax.random.PRNGKey(0), mesh=mesh)
        assert last_routed_cfg(n0).schedule == "parallel"
        n1 = traces.count
        _fit(sodm.SODMConfig(engine="dsvrg", dsvrg=base), x, y,
             jax.random.PRNGKey(0), mesh=mesh)
        assert last_routed_cfg(n1).schedule == "serial"

    def test_route_honors_outer_partition_strategy(self):
        """SODMConfig.partition_strategy carries onto the DSVRG route."""
        x, y = _data(M=128, d=5)
        base = dsvrg.DSVRGConfig(n_partitions=8, epochs=2, batch=8)
        _, r_strat = _fit(sodm.SODMConfig(engine="dsvrg", dsvrg=base),
                          x, y, jax.random.PRNGKey(3))
        _, r_rand = _fit(sodm.SODMConfig(engine="dsvrg",
                                         partition_strategy="random",
                                         dsvrg=base),
                         x, y, jax.random.PRNGKey(3))
        d_rand = dsvrg._solve(
            x, y, PARAMS,
            dataclasses.replace(base, partition_strategy="random"),
            jax.random.PRNGKey(3))
        assert jnp.array_equal(r_rand.raw.perm, d_rand.perm)
        assert not jnp.array_equal(r_strat.raw.perm, r_rand.raw.perm)


# ---------------------------------------------------------------------------
# trace-once pin of the epoch-scan drivers
# ---------------------------------------------------------------------------

class TestTraceOnce:
    def test_solve_traces_once_per_config(self):
        x, y = _data(M=96, d=5)
        cfg = dsvrg.DSVRGConfig(n_partitions=6, epochs=5, batch=4)
        n0 = dsvrg.epoch_trace_count()
        dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(0))
        assert dsvrg.epoch_trace_count() == n0 + 1
        # same config + shapes, different data: jit cache hit, no retrace
        dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        dsvrg._solve(-x, y, PARAMS, cfg, jax.random.PRNGKey(2))
        assert dsvrg.epoch_trace_count() == n0 + 1

    def test_sharded_traces_once_per_config(self):
        x, y = _data(M=96, d=5)
        mesh = _mesh1()
        cfg = dsvrg.DSVRGConfig(n_partitions=6, epochs=5, batch=4,
                                schedule="parallel")
        n0 = dsvrg.epoch_trace_count()
        dsvrg._solve_sharded(x, y, PARAMS, cfg, jax.random.PRNGKey(0), mesh)
        assert dsvrg.epoch_trace_count() == n0 + 1
        dsvrg._solve_sharded(x, y, PARAMS, cfg, jax.random.PRNGKey(1), mesh)
        assert dsvrg.epoch_trace_count() == n0 + 1

    def test_epoch_loop_is_a_scan(self):
        """The epochs ride a lax.scan of length cfg.epochs inside ONE
        jitted driver — not a host loop of per-epoch dispatches."""
        params = PARAMS
        cfg = dsvrg.DSVRGConfig(n_partitions=2, epochs=7, batch=4)
        xs = jnp.zeros((2, 3, 4, 5))
        ys = jnp.zeros((2, 3, 4))
        wts = jnp.ones((3, 4))
        jaxpr = jax.make_jaxpr(functools.partial(
            dsvrg._run.__wrapped__, params=params, cfg=cfg, M=24))(
                jnp.zeros(5), xs, ys, wts)
        assert f"length={cfg.epochs}" in str(jaxpr)
