"""Solver-engine layer: scalar / block / pallas SODM level solves.

Acceptance (ISSUE 1): the pallas engine (interpret mode on CPU) must match
the scalar engine's dual objective within 1e-3 on the synthetic SODM test
problem, honor Algorithm 1's warm starts (a warm-started parent solve takes
fewer passes than a cold start), and the sharded driver must solve every
level exactly once.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import sharding
from repro.core import engines, kernel_fns as kf, odm, sodm
from repro.kernels import ops


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


def _objective(spec, x, y, res, M):
    Q = kf.signed_gram(spec, x[res.perm], y[res.perm])
    return float(odm.dual_objective(Q, res.alpha, PARAMS, float(M)))


def _cfg(**kw):
    base = dict(p=2, levels=2, n_landmarks=4, tol=1e-6, max_sweeps=500)
    base.update(kw)
    return sodm.SODMConfig(**base)


class TestEngineParity:
    def test_block_matches_scalar(self):
        x, y = _data()
        o_s = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="scalar"),
            jax.random.PRNGKey(1)), 256)
        o_b = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="block", block=64),
            jax.random.PRNGKey(1)), 256)
        assert abs(o_s - o_b) < 1e-3, (o_s, o_b)

    def test_pallas_matches_scalar(self):
        x, y = _data()
        o_s = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="scalar"),
            jax.random.PRNGKey(1)), 256)
        o_p = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="pallas", block=64),
            jax.random.PRNGKey(1)), 256)
        assert abs(o_s - o_p) < 1e-3, (o_s, o_p)

    def test_pallas_matrix_free_u_refresh(self):
        """gram_threshold=0 forces the on-the-fly rbf_gram tile path for
        the u refresh; it must agree with the materialized-Q path."""
        x, y = _data()
        o_mat = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS,
            _cfg(engine="pallas", block=64, gram_threshold=10 ** 9),
            jax.random.PRNGKey(1)), 256)
        o_free = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS,
            _cfg(engine="pallas", block=64, gram_threshold=0),
            jax.random.PRNGKey(1)), 256)
        assert abs(o_mat - o_free) < 1e-4, (o_mat, o_free)

    def test_pallas_handles_non_tile_multiple_partitions(self):
        """m=72 with block=64 exercises the padded (masked) path."""
        M = 288
        x, y = _data(M=M)
        o_s = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="scalar"),
            jax.random.PRNGKey(1)), M)
        o_p = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, _cfg(engine="pallas", block=64),
            jax.random.PRNGKey(1)), M)
        assert abs(o_s - o_p) < 1e-3, (o_s, o_p)

    def test_unknown_engine_raises(self):
        with pytest.raises(ValueError):
            engines.make_local_solver("gauss")


class TestWarmStarts:
    def test_warm_start_takes_fewer_passes_than_cold(self):
        """Algorithm 1 line 12: the parent solve seeded from the merged
        child solutions must converge in fewer kernel passes than a cold
        start of the same problem. steps_per_pass is kept small so the
        pass count resolves the actual work (at the default 2B greedy
        steps per pass, tiny problems converge in a handful of passes
        either way and the difference vanishes into the granularity)."""
        M = 256
        x, y = _data(M=M)
        p = PARAMS
        # children: two independent half-problems (one SODM level)
        m = M // 2
        merged = []
        for k in range(2):
            sl = slice(k * m, (k + 1) * m)
            Qk = kf.signed_gram(SPEC, x[sl], y[sl])
            ak, _, _ = ops.dual_cd_solve(
                Qk, c=p.c, ups=p.ups, theta=p.theta, mscale=float(m),
                block=64, n_passes=200, tol=1e-6)
            merged.append(ak)
        # Algorithm 1 line 12 merge + the engines' warm-start conditioning
        # (exact line search along the ray; children were solved at scale
        # m, the parent at p·m — see the sodm module's scale note)
        warm0 = sodm.merge_alphas(jnp.stack(merged))
        Q = kf.signed_gram(SPEC, x, y)
        u0 = Q @ (warm0[:M] - warm0[M:])
        warm0 = warm0 * odm.warm_start_scale(u0, warm0, p, float(M))
        kw = dict(c=p.c, ups=p.ups, theta=p.theta, mscale=float(M),
                  block=64, n_passes=500, tol=1e-6, steps_per_pass=16)
        _, _, cold = ops.dual_cd_solve(Q, **kw)
        _, _, warm = ops.dual_cd_solve(Q, alpha0=warm0, **kw)
        assert int(warm) < int(cold), (int(warm), int(cold))

    def test_engine_warm_start_no_worse_than_cold(self):
        """End-to-end via the engine: the warm-started final level must not
        need more passes than a cold solve of the full problem."""
        M = 256
        x, y = _data(M=M)
        cfg = _cfg(engine="pallas", block=64)
        res = sodm._solve(SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        Q = kf.signed_gram(SPEC, x[res.perm], y[res.perm])
        p = PARAMS
        _, _, cold = ops.dual_cd_solve(
            Q, c=p.c, ups=p.ups, theta=p.theta, mscale=float(M), block=64,
            n_passes=200, tol=1e-6)
        assert res.sweeps_per_level[-1] <= int(cold)

    def test_converged_warm_start_is_zero_passes(self):
        M = 128
        x, y = _data(M=M)
        Q = kf.signed_gram(SPEC, x, y)
        p = PARAMS
        alpha, _, _ = ops.dual_cd_solve(
            Q, c=p.c, ups=p.ups, theta=p.theta, mscale=float(M), block=64,
            n_passes=200, tol=1e-6)
        _, _, passes = ops.dual_cd_solve(
            Q, c=p.c, ups=p.ups, theta=p.theta, mscale=float(M), block=64,
            n_passes=200, tol=1e-6, alpha0=alpha)
        assert int(passes) == 0


class TestRbfGramMatvec:
    def test_matches_dense_product(self):
        key = jax.random.PRNGKey(0)
        K, m, d = 3, 72, 10            # non-tile-multiple m
        x = jax.random.normal(key, (K, m, d))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (K, m)))
        g = jax.random.normal(jax.random.fold_in(key, 2), (K, m))
        u = ops.rbf_gram_matvec(x, g, gamma=0.7, y=y, bm=32, bn=32)
        ref = jnp.stack([
            kf.signed_gram(kf.KernelSpec("rbf", 0.7), x[k], y[k]) @ g[k]
            for k in range(K)])
        assert float(jnp.max(jnp.abs(u - ref))) < 1e-4


class TestWarmStartScaleRegression:
    def test_ray_search_beats_plain_and_inverse_p_scaling(self):
        """odm.warm_start_scale: on a constructed parent/child merge the
        closed-form ray search must land a strictly better dual objective
        than BOTH naive corrections — t = 1 (plain concatenation) and
        t = 1/p (pure regularizer-scale heuristic). lam is picked so the
        parent sits between the regularizer-dominant and Q-dominant
        regimes, where neither naive scale is optimal."""
        M, p_merge = 256, 2
        x, y = _data(M=M)
        params = odm.ODMParams(lam=10.0, theta=0.1, ups=0.5)
        m = M // p_merge
        merged = []
        for k in range(p_merge):
            sl = slice(k * m, (k + 1) * m)
            Qk = kf.signed_gram(SPEC, x[sl], y[sl])
            ak, _, _ = ops.dual_cd_solve(
                Qk, c=params.c, ups=params.ups, theta=params.theta,
                mscale=float(m), block=64, n_passes=300, tol=1e-7)
            merged.append(ak)
        warm = sodm.merge_alphas(jnp.stack(merged))
        Q = kf.signed_gram(SPEC, x, y)
        u = Q @ (warm[:M] - warm[M:])
        t = float(odm.warm_start_scale(u, warm, params, float(M)))
        assert 1.0 / p_merge < t < 1.0, t

        def obj(scale):
            return float(odm.dual_objective(Q, warm * scale, params,
                                            float(M)))

        f_star, f_one, f_inv = obj(t), obj(1.0), obj(1.0 / p_merge)
        assert f_star < f_one - 1e-9, (f_star, f_one)
        assert f_star < f_inv - 1e-9, (f_star, f_inv)

    def test_cold_start_is_identity(self):
        """A zero init must pass through unscaled (t = 1)."""
        zeros = jnp.zeros(64)
        t = odm.warm_start_scale(jnp.zeros(32), zeros, PARAMS, 32.0)
        assert float(t) == 1.0


class TestLineSearchSafeguard:
    def test_no_nan_at_weak_regularization_pr1_regression(self):
        """PR 1 regression, pinned: undamped Jacobi tile updates diverge to
        NaN when the off-diagonal Gram mass beats the m·c·I shift (weak
        regularization, lam large => c small). The exact line search along
        the joint step must keep every pass finite and descending — for
        the pure-jnp block oracle AND the fused pallas pass."""
        M = 192
        x, y = _data(M=M)
        weak = odm.ODMParams(lam=1e4, theta=0.1, ups=0.5)
        Q = kf.signed_gram(SPEC, x, y)
        from repro.core import dual_cd
        res = dual_cd.solve_block(Q, weak, mscale=float(M), block=32,
                                  tol=1e-6, max_outer=200)
        assert bool(jnp.all(jnp.isfinite(res.alpha))), "block oracle NaN"
        a_p, kkt, _ = ops.dual_cd_solve(
            Q, c=weak.c, ups=weak.ups, theta=weak.theta, mscale=float(M),
            block=32, n_passes=200, tol=1e-6)
        assert bool(jnp.all(jnp.isfinite(a_p))), "pallas NaN"
        assert float(kkt) < 1e-4, float(kkt)
        f0 = float(odm.dual_objective(Q, jnp.zeros(2 * M), weak, float(M)))
        f1 = float(odm.dual_objective(Q, a_p, weak, float(M)))
        assert f1 < f0, (f1, f0)


class TestFusedPassOpCount:
    def test_exactly_one_pallas_call_per_pass(self):
        """Acceptance: the fused pass loop issues exactly ONE pallas_call
        per pass — tile sweeps and the Gram matvec together — on both the
        dense and the matrix-free path (the PR 1 layout used two kernel
        launches: the sweep + a separate matvec)."""
        from repro.kernels import dual_cd_block as cdk, gram as gram_mod

        K, m, B, d = 2, 64, 32, 8
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (K, m, d))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (K, m)))
        qb = jax.vmap(lambda q: cdk.extract_diag_blocks(q, B))(
            jax.vmap(lambda xk, yk: kf.signed_gram(SPEC, xk, yk))(x, y))
        a = jnp.zeros((K, m // B, 2 * B))
        u = jnp.zeros((K, m // B, B))
        v = jnp.ones((K, m // B, B))
        p = PARAMS

        srcs = {
            "dense": gram_mod.DenseSource(
                jax.vmap(lambda xk, yk: kf.signed_gram(SPEC, xk, yk))(x, y)),
            "mfree": gram_mod.make_kernel_source(SPEC, x, y, bm=B, bn=B,
                                                 interpret=True),
        }
        for name, src in srcs.items():
            calls = ops.count_pallas_calls(lambda src=src: cdk.fused_cd_pass(
                qb, src, a, u, v, c=p.c, ups=p.ups, theta=p.theta,
                mscale=float(m), n_steps=2 * B, exit_tol=0.0,
                interpret=True))
            assert calls == 1, (name, calls)


class TestFusedPassNumericalParity:
    @staticmethod
    def _both_layouts(source, m):
        from repro.kernels import dual_cd_block as cdk, gram as gram_mod

        K, B, d = 2, 16, 6
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, (K, m, d))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (K, m)))
        Qs = jax.vmap(lambda xk, yk: kf.signed_gram(SPEC, xk, yk))(x, y)
        qb = jax.vmap(lambda q: cdk.extract_diag_blocks(q, B))(Qs)
        if source == "dense":
            src = gram_mod.DenseSource(Qs)
        else:
            src = gram_mod.make_kernel_source(SPEC, x, y, bm=B, bn=B,
                                              interpret=True)
        p = PARAMS
        outs = {}
        for fused in (True, False):
            a, kkts, passes, counts = cdk.solve_level(
                qb, src, jnp.zeros((K, 2 * m)), c=p.c, ups=p.ups,
                theta=p.theta, mscale=float(m), n_passes=100, tol=1e-6,
                fused=fused, interpret=True)
            outs[fused] = (a, int(passes), counts)
        assert outs[True][1] == outs[False][1]
        err = float(jnp.max(jnp.abs(outs[True][0] - outs[False][0])))
        assert err < 1e-6, err
        return outs

    @pytest.mark.parametrize("source", ["dense", "mfree"])
    def test_fused_equals_two_launch_layout(self, source):
        """The fused pass and the two-launch layout run the same math —
        solve_level(fused=True) must reproduce fused=False bit-for-bit-ish
        on both gram sources (the TPU path vs the interpret-mode path)."""
        self._both_layouts(source, 64)

    @pytest.mark.parametrize("source", ["dense", "mfree"])
    def test_ragged_groups_fused_equals_two_launch_layout(self, source):
        """Five tiles a partition, one past a whole lockstep group: both
        layouts still agree in passes and duals, and each counts its
        applied steps within the step slots its groups held (the last
        bits of their matvecs differ, so the tiles' exits may too)."""
        outs = self._both_layouts(source, 80)
        for _, _, counts in outs.values():
            assert bool(jnp.all(counts[:, 0] > 0))
            assert bool(jnp.all(counts[:, 0] <= counts[:, 1]))


class TestMaterializedFallbackWarning:
    def test_warns_once_with_memory_estimate(self, monkeypatch):
        """A kernel without a matrix-free lowering above gram_threshold
        must warn (once, with the memory estimate) instead of silently
        materializing the O(m²) Gram."""
        import warnings as _warnings
        from repro.kernels import gram as gram_mod

        monkeypatch.setattr(gram_mod, "MATRIX_FREE_KERNELS", ("rbf",))
        monkeypatch.setattr(engines, "_MATERIALIZED_WARNED", set())
        K, m, d = 2, 48, 5
        key = jax.random.PRNGKey(0)
        xs = jax.random.normal(key, (K, m, d))
        ys = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1), (K, m)))
        a0 = jnp.zeros((K, 2 * m))
        spec = kf.make_spec("poly", gamma=0.2, degree=2)
        with _warnings.catch_warnings(record=True) as caught:
            _warnings.simplefilter("always")
            for _ in range(2):
                engines.solve_level_pallas(
                    xs, ys, a0, spec=spec, params=PARAMS, tol=1e-4,
                    max_sweeps=50, block=16, gram_threshold=0)
        relevant = [w for w in caught
                    if "matrix-free" in str(w.message)]
        assert len(relevant) == 1, [str(w.message) for w in caught]
        assert "GiB" in str(relevant[0].message)

    def test_all_spec_kernels_have_matrix_free_path(self):
        """After the tentpole no KernelSpec family may hit the fallback."""
        from repro.kernels import gram as gram_mod
        assert set(kf.KERNELS) <= set(gram_mod.MATRIX_FREE_KERNELS)


class TestShardedAccounting:
    def test_tail_not_resolved_twice_and_levels_run_true(self):
        """Regression: with a 1-device mesh the old driver re-solved the
        K == 1 level in the replicated tail and hard-coded
        levels_run = cfg.levels + 1."""
        M = 128
        x, y = _data(M=M)
        mesh = sharding.make_mesh((1,), ("data",))
        cfg = _cfg(levels=2)
        res = sodm._solve_sharded(SPEC, x, y, PARAMS, cfg,
                                  jax.random.PRNGKey(1), mesh,
                                  data_axis="data")
        # levels+1 level solves (L, L-1, ..., 0), each exactly once
        assert len(res.sweeps_per_level) == cfg.levels + 1
        assert res.levels_run == len(res.sweeps_per_level)
        o_sh = _objective(SPEC, x, y, res, M)
        o_ref = _objective(SPEC, x, y, sodm._solve(
            SPEC, x, y, PARAMS, cfg, jax.random.PRNGKey(1)), M)
        assert abs(o_sh - o_ref) < 1e-3, (o_sh, o_ref)

    def test_levels_run_honest_under_early_stop(self):
        """levels_run must equal the number of level solves actually run,
        also in the single-process driver."""
        M = 128
        x, y = _data(M=M)
        res = sodm._solve(SPEC, x, y, PARAMS, _cfg(levels=2),
                          jax.random.PRNGKey(1))
        assert res.levels_run == len(res.sweeps_per_level)
