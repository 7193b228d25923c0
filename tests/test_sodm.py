"""SODM Algorithm 1: hierarchical merge, warm starts, convergence."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import baselines, dual_cd, kernel_fns as kf, odm, sodm
from repro.data import synthetic


def _data(M=256, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.concatenate([jax.random.normal(k1, (M // 2, d)) + 1.0,
                         jax.random.normal(k2, (M // 2, d)) - 1.0])
    y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
    perm = jax.random.permutation(k3, M)
    return x[perm], y[perm]


PARAMS = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)
SPEC = kf.KernelSpec(name="rbf", gamma=0.5)


class TestSODM:
    def test_matches_global_solve(self):
        x, y = _data()
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-6,
                              max_sweeps=500)
        res = sodm._solve(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        xp, yp = x[res.perm], y[res.perm]
        Q = kf.signed_gram(SPEC, xp, yp)
        glob = dual_cd.solve(Q, PARAMS, mscale=256.0, tol=1e-6,
                             max_sweeps=500)
        o1 = odm.dual_objective(Q, res.alpha, PARAMS, 256.0)
        o2 = odm.dual_objective(Q, glob.alpha, PARAMS, 256.0)
        assert abs(float(o1 - o2)) < 1e-4

    @pytest.mark.parametrize("engine", ["pallas", "scalar"])
    def test_level_spans_carry_kernel_counts_of_the_pallas_engine(self,
                                                                  engine):
        """The pallas engine's level spans carry its kernel's step counts
        (engines.LEVEL_COUNTERS); other engines set none."""
        from repro.core import engines
        from repro.observe import SpanRecorder, install
        x, y = _data(M=128)
        cfg = sodm.SODMConfig(engine=engine, p=2, levels=1, n_landmarks=4,
                              tol=1e-4, max_sweeps=50)
        rec = SpanRecorder()
        with install(rec):
            sodm._solve(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        levels = [e["args"] for e in rec.spans("cascade.level")]
        assert len(levels) == 2
        for lv in levels:
            has = set(engines.LEVEL_COUNTERS) <= set(lv)
            assert has == (engine == "pallas")
            if has:
                assert 0 < lv["tile_steps"] <= lv["step_slots"]

    def test_merge_alphas_layout(self):
        alphas = jnp.arange(12.0).reshape(2, 6)   # 2 parts, m=3
        merged = sodm.merge_alphas(alphas)
        # zetas: [0,1,2] + [6,7,8]; betas: [3,4,5] + [9,10,11]
        want = jnp.array([0, 1, 2, 6, 7, 8, 3, 4, 5, 9, 10, 11.0])
        assert bool(jnp.all(merged == want))

    def test_split_inverts_merge(self):
        alphas = jax.random.uniform(jax.random.PRNGKey(0), (4, 10))
        merged = sodm.merge_alphas(alphas)
        back = sodm.split_to_partitions(merged, 4)
        assert float(jnp.max(jnp.abs(back - alphas))) == 0.0

    def test_warm_start_reduces_sweeps(self):
        """Warm-started later levels should converge in fewer sweeps than a
        cold global solve."""
        x, y = _data(M=256)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-6,
                              max_sweeps=500)
        res = sodm._solve(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        xp, yp = x[res.perm], y[res.perm]
        Q = kf.signed_gram(SPEC, xp, yp)
        cold = dual_cd.solve(Q, PARAMS, mscale=256.0, tol=1e-6,
                             max_sweeps=500)
        # last level ran on the full problem with a warm start
        assert res.sweeps_per_level[-1] <= int(cold.sweeps)

    def test_generalization_close_to_global(self):
        ds = synthetic.load("svmguide1", scale=0.05)
        x, y = ds.x_train, ds.y_train
        M = x.shape[0] - x.shape[0] % 8
        x, y = x[:M], y[:M]
        # features normalized to [0,1]: gamma must be larger than the
        # blob-scale default used by the other tests
        spec = kf.KernelSpec(name="rbf", gamma=2.0)
        params = odm.ODMParams(lam=10.0, theta=0.1, ups=0.5)
        cfg = sodm.SODMConfig(p=2, levels=3, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        res = sodm._solve(spec, x, y, params, cfg, jax.random.PRNGKey(2))
        pred = sodm.predict(spec, res, x, y, ds.x_test)
        acc = float(odm.accuracy(ds.y_test, pred))
        assert acc > 0.85, acc

    def test_level_loop_never_reroutes(self):
        """The level loop only runs levels: a linear kernel with the
        engine unset and M above ``dsvrg_threshold`` still runs all
        levels + 1 level solves (the route is the registry's choice)."""
        x, y = _data(M=128)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-4,
                              max_sweeps=50, early_stop=False,
                              dsvrg_threshold=64)
        assert cfg.engine is None
        n0 = sodm.level_solve_count()
        res = sodm._solve(kf.KernelSpec(name="linear"), x, y, PARAMS, cfg,
                          jax.random.PRNGKey(0))
        assert res.levels_run == cfg.levels + 1
        assert sodm.level_solve_count() - n0 == cfg.levels + 1

    def test_partition_strategies_run(self):
        x, y = _data(M=128)
        for strat in ("stratified", "random", "cluster", "identity"):
            cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4,
                                  partition_strategy=strat, tol=1e-5,
                                  max_sweeps=200)
            res = sodm._solve(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(3))
            assert res.alpha.shape == (256,)


class TestBaselines:
    def test_cascade_accuracy(self):
        x, y = _data(M=256)
        res = baselines._cascade_solve(SPEC, x, y, PARAMS, levels=2,
                                       key=jax.random.PRNGKey(0))
        pred = baselines.cascade_predict(SPEC, res, x)
        assert float(odm.accuracy(y, pred)) > 0.9

    def test_dip_dc_run_and_predict(self):
        x, y = _data(M=256)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=200)
        for solver in (baselines._dip_solve, baselines._dc_solve):
            res = solver(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
            pred = sodm.predict(SPEC, res, x, y, x)
            assert float(odm.accuracy(y, pred)) > 0.9

    def test_gradient_baselines_converge(self):
        x, y = _data(M=256, d=8)
        svrg = baselines._svrg_solve(x, y, PARAMS, epochs=6, eta=0.05,
                                     key=jax.random.PRNGKey(0), batch=8)
        csvrg = baselines._csvrg_solve(x, y, PARAMS, epochs=6, eta=0.05,
                                       key=jax.random.PRNGKey(0),
                                       coreset_frac=0.25, batch=8)
        assert float(svrg.history[-1]) < float(svrg.history[0])
        assert float(csvrg.history[-1]) < float(csvrg.history[0])
