"""Unified-API battery: spec validation, registry capability errors,
resolve-policy property tests vs the paper's linear-kernel dispatch,
estimator parity with the route drivers it calls (bit-identical
predictions), and artifact round trips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ODMEstimator, ProblemSpec, registry
from repro.api.registry import SolverEntry
from repro.core import dsvrg, kernel_fns as kf, odm, sodm


def _data(M=128, d=5, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.concatenate([jax.random.normal(k1, (M // 2, d)) + 1.0,
                         jax.random.normal(k2, (M // 2, d)) - 1.0])
    y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
    perm = jax.random.permutation(k3, M)
    return x[perm], y[perm]


PARAMS = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)
RBF = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5),
                  params=PARAMS)
LIN = ProblemSpec(kernel=kf.KernelSpec(name="linear"), params=PARAMS)
CFG = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                      max_sweeps=200)
DCFG = sodm.SODMConfig(dsvrg=dsvrg.DSVRGConfig(n_partitions=8, epochs=4,
                                               batch=8))


# ---------------------------------------------------------------------------
# ProblemSpec: eager validation
# ---------------------------------------------------------------------------

class TestProblemSpec:
    def test_bad_hyperparameters_raise_eagerly(self):
        with pytest.raises(ValueError, match="kernel"):
            ProblemSpec(kernel=kf.KernelSpec(name="sigmoid"))
        with pytest.raises(ValueError, match="gamma"):
            ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.0))
        with pytest.raises(ValueError, match="degree"):
            ProblemSpec(kernel=kf.KernelSpec(name="poly", degree=0))
        with pytest.raises(ValueError, match="lam"):
            ProblemSpec(params=odm.ODMParams(lam=0.0))
        with pytest.raises(ValueError, match="theta"):
            ProblemSpec(params=odm.ODMParams(theta=1.0))
        with pytest.raises(ValueError, match="ups"):
            ProblemSpec(params=odm.ODMParams(ups=-1.0))

    def test_create_convenience(self):
        p = ProblemSpec.create("poly", gamma=0.3, degree=2, lam=10.0)
        assert p.kernel.name == "poly" and p.kernel.degree == 2
        assert p.params.lam == 10.0

    def test_data_validation(self):
        x, y = _data(M=32)
        with pytest.raises(ValueError, match=r"\(M, d\)"):
            RBF.validate(x[:, 0], y)
        with pytest.raises(ValueError, match="disagree"):
            RBF.validate(x, y[:-2])
        with pytest.raises(ValueError, match=r"\+1/-1"):
            RBF.validate(x, jnp.where(y > 0, 1.0, 0.0))
        xv, yv = RBF.validate(x, y.astype(jnp.int32))
        assert yv.dtype == x.dtype            # int labels are cast

    def test_spec_is_hashable_static(self):
        assert hash(RBF) != hash(LIN)


# ---------------------------------------------------------------------------
# registry: capability errors (satellite: no silent fallbacks)
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_routes_registered(self):
        assert set(registry.routes()) == {"sodm", "dsvrg", "cascade",
                                          "dip", "dc", "svrg", "csvrg"}

    def test_duplicate_registration_raises(self):
        entry = SolverEntry(name="sodm", fit=lambda *a, **k: None,
                            algorithm="dup")
        with pytest.raises(ValueError, match="already registered"):
            registry.register(entry)
        # the error lists the existing routes so the clash is debuggable
        try:
            registry.register(entry)
        except ValueError as e:
            assert "sodm" in str(e) and "dsvrg" in str(e)

    def test_register_unregister_round_trip(self):
        entry = SolverEntry(name="_test_route", fit=lambda *a, **k: None,
                            algorithm="test")
        registry.register(entry)
        try:
            assert registry.get("_test_route") is entry
        finally:
            registry.unregister("_test_route")
        with pytest.raises(ValueError, match="unknown route"):
            registry.get("_test_route")

    def test_unknown_route_lists_options(self):
        with pytest.raises(ValueError, match="registered routes"):
            registry.resolve(RBF, 100, route="bogus")

    def test_unsupported_kernel_lists_capabilities(self):
        for route in ("dsvrg", "svrg", "csvrg"):
            with pytest.raises(ValueError) as ei:
                registry.resolve(RBF, 100, route=route)
            msg = str(ei.value)
            assert "linear" in msg               # the supported family
            assert "capabilities" in msg
            assert "sodm" in msg                 # routes that DO support rbf

    def test_mesh_on_mesh_unaware_route_raises(self):
        from repro.sharding import make_mesh
        mesh = make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="mesh"):
            registry.resolve(RBF, 100, route="cascade", mesh=mesh)
        # mesh-aware routes accept the same mesh
        assert registry.resolve(RBF, 100, route="sodm",
                                mesh=mesh).name == "sodm"

    def test_estimator_rejects_unknown_route_eagerly(self):
        with pytest.raises(ValueError, match="unknown route"):
            ODMEstimator(RBF, route="bogus")


# ---------------------------------------------------------------------------
# resolve policy == the PR 3 dispatch (property battery)
# ---------------------------------------------------------------------------

def _legacy_wants_dsvrg(engine, kernel_name, M, threshold):
    """The paper's linear-kernel dispatch rule, written out apart from
    the registry (reference)."""
    if engine == "dsvrg":
        if kernel_name != "linear":
            raise ValueError("linear required")
        return True
    return engine is None and kernel_name == "linear" and M >= threshold


class TestResolvePolicy:
    ENGINES = (None, "scalar", "block", "pallas", "dsvrg")
    KERNELS = ("linear", "rbf", "laplacian", "poly")
    BANDS = ((10, 5), (10, 50), (199_999, 200_000), (200_000, 200_000),
             (1, 1), (10 ** 7, 200_000))

    def test_matches_legacy_dispatch_exhaustively(self):
        """Full cartesian sweep: the registry's auto policy reproduces the
        PR 3 behavior bit for bit, including the nonlinear-dsvrg error."""
        for engine in self.ENGINES:
            for kernel in self.KERNELS:
                for M, thr in self.BANDS:
                    try:
                        want = _legacy_wants_dsvrg(engine, kernel, M, thr)
                    except ValueError:
                        with pytest.raises(ValueError, match="linear"):
                            registry.resolve_auto(kernel, M, engine=engine,
                                                  threshold=thr)
                        continue
                    entry = registry.resolve_auto(kernel, M, engine=engine,
                                                  threshold=thr)
                    assert (entry.name == "dsvrg") == want, \
                        (engine, kernel, M, thr)

    def test_explicit_engine_never_rerouted(self):
        for engine in ("scalar", "block", "pallas"):
            e = registry.resolve_auto("linear", 10 ** 9, engine=engine,
                                      threshold=1)
            assert e.name == "sodm"

    def test_linear_above_threshold_auto_routes(self):
        assert registry.resolve_auto("linear", 200_000).name == "dsvrg"
        assert registry.resolve_auto("linear", 199_999).name == "sodm"

    def test_nonlinear_never_auto_routes(self):
        for kernel in ("rbf", "laplacian", "poly"):
            assert registry.resolve_auto(kernel, 10 ** 9,
                                         threshold=1).name == "sodm"

    def test_resolve_reads_config(self):
        cfg = sodm.SODMConfig(dsvrg_threshold=64)
        assert registry.resolve(LIN, 128, cfg=cfg).name == "dsvrg"
        assert registry.resolve(LIN, 32, cfg=cfg).name == "sodm"
        pinned = sodm.SODMConfig(engine="scalar", dsvrg_threshold=64)
        assert registry.resolve(LIN, 128, cfg=pinned).name == "sodm"
        # explicit route beats everything the config says
        assert registry.resolve(LIN, 8, route="dsvrg",
                                cfg=pinned).name == "dsvrg"


# ---------------------------------------------------------------------------
# estimator: parity with the route drivers (bit-identical)
# ---------------------------------------------------------------------------

class TestEstimatorParity:
    def test_sodm_route_bit_identical(self):
        x, y = _data()
        key = jax.random.PRNGKey(1)
        model, rep = ODMEstimator(RBF, route="sodm", cfg=CFG).fit(x, y, key)
        res = sodm._solve(RBF.kernel, x, y, PARAMS, CFG, key)
        legacy_pred = sodm.predict(RBF.kernel, res, x, y, x)
        assert np.array_equal(np.asarray(model.predict(x)),
                              np.asarray(legacy_pred))
        assert np.array_equal(np.asarray(rep.raw.alpha),
                              np.asarray(res.alpha))
        assert rep.route == "sodm" and rep.passes == \
            tuple(res.sweeps_per_level)

    def test_dsvrg_route_bit_identical(self):
        x, y = _data()
        key = jax.random.PRNGKey(2)
        model, rep = ODMEstimator(LIN, route="dsvrg", cfg=DCFG).fit(
            x, y, key)
        dres = dsvrg._solve(x, y, PARAMS, DCFG.dsvrg, key)
        assert np.array_equal(np.asarray(model.w), np.asarray(dres.w))
        assert np.array_equal(np.asarray(model.predict(x)),
                              np.asarray(jnp.sign(x @ dres.w)))
        assert rep.eta == pytest.approx(float(dres.eta))
        assert rep.history == tuple(float(h) for h in dres.history)

    def test_auto_route_end_to_end(self):
        """Tiny threshold: the facade lands on dsvrg where the paper's
        auto dispatch says, and reports it."""
        x, y = _data()
        auto_cfg = dataclasses.replace(DCFG, dsvrg_threshold=64)
        _, rep = ODMEstimator(LIN, cfg=auto_cfg).fit(x, y)
        assert rep.route == "dsvrg"
        pinned = dataclasses.replace(auto_cfg, engine="scalar",
                                     p=2, levels=2)
        _, rep2 = ODMEstimator(LIN, cfg=pinned).fit(x, y)
        assert rep2.route == "sodm" and len(rep2.passes) == 3

    def test_baseline_routes_fit_and_score(self):
        x, y = _data()
        for route in ("cascade", "dip", "dc"):
            est = ODMEstimator(RBF, route=route, cfg=CFG)
            model, rep = est.fit(x, y, jax.random.PRNGKey(3))
            assert est.score(x, y) > 0.9, route
            assert rep.route == route and rep.wall_clock > 0
            assert rep.engine == "scalar", route      # engine=None
        for route in ("svrg", "csvrg"):
            est = ODMEstimator(LIN, route=route, cfg=DCFG)
            model, rep = est.fit(x, y, jax.random.PRNGKey(3))
            assert est.score(x, y) > 0.9, route
            assert model.w is not None and rep.eta > 0
            assert rep.engine == route
            assert rep.history[-1] < rep.history[0]

    def test_explicit_routes_reject_dsvrg_engine(self):
        """An explicit non-dsvrg route with SODMConfig.engine='dsvrg' is
        contradictory and fails loudly — never a silent re-route through
        the level loop's own dispatch (or a silently ignored pin)."""
        x, y = _data(M=32)
        cfg = dataclasses.replace(DCFG, engine="dsvrg", levels=2,
                                  n_landmarks=4)
        for route in ("sodm", "dip", "dc", "cascade", "svrg", "csvrg"):
            with pytest.raises(ValueError, match="contradictory"):
                ODMEstimator(LIN, route=route, cfg=cfg).fit(x, y)
        # the same engine pin WITH the matching route is of course fine
        ODMEstimator(LIN, route="dsvrg", cfg=cfg).fit(x, y)

    def test_gradient_routes_reject_nonlinear(self):
        x, y = _data(M=32)
        for route in ("svrg", "csvrg", "dsvrg"):
            with pytest.raises(ValueError, match="linear"):
                ODMEstimator(RBF, route=route, cfg=DCFG).fit(x, y)

    def test_report_uniform_fields(self):
        x, y = _data()
        _, rep = ODMEstimator(RBF, route="sodm", cfg=CFG).fit(x, y)
        assert rep.n_train == x.shape[0]
        assert rep.n_sv > 0 and rep.compression in ("exact", "pruned")
        assert rep.kkt is not None and rep.kkt <= CFG.tol * 1.01
        assert "route=sodm" in rep.summary()
        assert rep.engine == "scalar"                 # engine=None
        assert isinstance(rep.raw, sodm.SODMResult)

    def test_unfitted_estimator_raises(self):
        est = ODMEstimator(RBF)
        with pytest.raises(ValueError, match="not fitted"):
            est.predict(jnp.zeros((2, 5)))


# ---------------------------------------------------------------------------
# estimator: persistence round trip
# ---------------------------------------------------------------------------

class TestPersistence:
    def test_save_load_predict_round_trip(self, tmp_path):
        x, y = _data()
        est = ODMEstimator(RBF, route="sodm", cfg=CFG)
        est.fit(x, y, jax.random.PRNGKey(4))
        est.save(str(tmp_path))
        loaded = ODMEstimator.load(str(tmp_path))
        assert np.array_equal(np.asarray(est.predict(x)),
                              np.asarray(loaded.predict(x)))
        assert loaded.problem.kernel == RBF.kernel
        assert loaded.model_.compression == est.model_.compression

    def test_save_load_linear_route(self, tmp_path):
        x, y = _data()
        est = ODMEstimator(LIN, route="dsvrg", cfg=DCFG)
        est.fit(x, y, jax.random.PRNGKey(5))
        est.save(str(tmp_path))
        loaded = ODMEstimator.load(str(tmp_path))
        assert np.array_equal(np.asarray(est.model_.w),
                              np.asarray(loaded.model_.w))

    def test_compression_knobs_forward(self):
        x, y = _data()
        est = ODMEstimator(RBF, route="sodm", cfg=CFG, budget=16)
        model, rep = est.fit(x, y, jax.random.PRNGKey(6))
        assert model.n_sv <= 16
        assert rep.compression == "nystrom"
