"""Serving subsystem (PR 4): tiled scorer parity, compiled FittedODM
artifacts across every kernel family and solver route, compression
accuracy, checkpoint round trips, compile-once predict, microbatching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.core import baselines, kernel_fns as kf, odm, sodm
from repro.data import synthetic
from repro.kernels import ops, score

KEY = jax.random.PRNGKey(0)

PARAMS = odm.ODMParams(lam=1.0, theta=0.1, ups=0.5)

ALL_SPECS = [kf.KernelSpec("linear"), kf.KernelSpec("rbf", 0.5),
             kf.KernelSpec("laplacian", 0.3),
             kf.KernelSpec("poly", 0.5, 2, 1.0)]


def _blobs(M=128, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.concatenate([jax.random.normal(k1, (M // 2, d)) + 1.0,
                         jax.random.normal(k2, (M // 2, d)) - 1.0])
    y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
    perm = jax.random.permutation(k3, M)
    return x[perm], y[perm]


def _rel_gap(got, want, tol=1e-5):
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    return float(jnp.max(jnp.abs(got - want))) / scale


# ---------------------------------------------------------------------------
# the tiled decision-function kernel
# ---------------------------------------------------------------------------

class TestScoreKernel:
    @pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
    @pytest.mark.parametrize("T,S,D", [(64, 96, 32), (70, 45, 33)])
    def test_tiled_matches_ref(self, spec, T, S, D):
        x = jax.random.normal(KEY, (T, D))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (S, D))
        c = jax.random.normal(jax.random.fold_in(KEY, 2), (S,))
        want = score.score_ref(x, z, c, kind=spec.name, gamma=spec.gamma,
                               degree=spec.degree, coef0=spec.coef0)
        got = ops.decision_scores(x, z, c, spec, bt=32, bs=32, tiled=True)
        assert _rel_gap(got, want) < 1e-5, spec.name

    @pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
    def test_blocked_matches_ref(self, spec):
        T, S, D = 70, 45, 17
        x = jax.random.normal(KEY, (T, D))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (S, D))
        c = jax.random.normal(jax.random.fold_in(KEY, 2), (S,))
        want = score.score_ref(x, z, c, kind=spec.name, gamma=spec.gamma,
                               degree=spec.degree, coef0=spec.coef0)
        got = ops.decision_scores(x, z, c, spec, bt=32, tiled=None)
        assert _rel_gap(got, want) < 1e-5, spec.name

    def test_one_pallas_call_per_batch(self):
        """Serving acceptance: one request batch = ONE kernel launch."""
        x = jax.random.normal(KEY, (64, 16))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (96, 16))
        c = jax.random.normal(jax.random.fold_in(KEY, 2), (96,))
        score.score_tiles.clear_cache()
        n = ops.count_pallas_calls(lambda: score.score_tiles(
            x, z, c, kind="rbf", gamma=0.5, bt=32, bs=32, bd=16,
            interpret=True))
        assert n == 1, n

    def test_zero_coef_padding_is_transparent(self):
        """Padded SV rows carry zero coef => identical scores."""
        x = jax.random.normal(KEY, (40, 12))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (30, 12))
        c = jax.random.normal(jax.random.fold_in(KEY, 2), (30,))
        spec = kf.KernelSpec("rbf", 0.7)
        base = ops.decision_scores(x, z, c, spec, bt=16, bs=16, tiled=True)
        zp = jnp.concatenate([z, jax.random.normal(KEY, (10, 12))])
        cp = jnp.concatenate([c, jnp.zeros(10)])
        padded = ops.decision_scores(x, zp, cp, spec, bt=16, bs=16,
                                     tiled=True)
        assert _rel_gap(padded, base) < 1e-6


# ---------------------------------------------------------------------------
# compiled artifacts: every kernel family, every solver route
# ---------------------------------------------------------------------------

class TestFittedODMParity:
    @pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
    def test_every_kernel_family_scalar_route(self, spec):
        x, y = _blobs()
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300, engine="scalar")
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        xp, yp = x[res.perm], y[res.perm]
        want = odm.decision_function(spec, xp, yp, res.alpha, x)
        model = serve.from_sodm(spec, res, x, y)
        assert _rel_gap(model.decision_function(x), want) < 1e-5
        if spec.name == "linear":
            assert model.w is not None and model.compression == "linear"
        else:
            assert model.n_sv <= model.n_train

    @pytest.mark.parametrize("engine", ["block", "pallas"])
    def test_engine_routes(self, engine):
        spec = kf.KernelSpec("rbf", 0.5)
        x, y = _blobs()
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300, engine=engine, block=64)
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(1))
        xp, yp = x[res.perm], y[res.perm]
        want = odm.decision_function(spec, xp, yp, res.alpha, x)
        model = serve.from_sodm(spec, res, x, y)
        assert _rel_gap(model.decision_function(x), want) < 1e-5

    def test_dsvrg_route_is_born_compressed(self):
        from repro.api import registry
        spec = kf.KernelSpec("linear")
        x, y = _blobs(M=128, d=8)
        cfg = sodm.SODMConfig(engine="dsvrg")
        res, _ = registry._solve_dsvrg(x, y, PARAMS, cfg,
                                       jax.random.PRNGKey(1))
        xp, yp = x[res.perm], y[res.perm]
        want = odm.decision_function(spec, xp, yp, res.alpha, x)
        model = serve.from_sodm(spec, res, x, y)
        assert model.w is not None            # linear collapse: O(d) scoring
        assert _rel_gap(model.decision_function(x), want) < 1e-5

    def test_from_dsvrg_direct(self):
        from repro.core import dsvrg
        x, y = _blobs(M=128, d=8)
        cfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=8, batch=16)
        res = dsvrg._solve(x, y, PARAMS, cfg, jax.random.PRNGKey(7))
        model = serve.from_dsvrg(res)
        assert model.n_train == 128
        assert model.compression == "linear"
        assert float(jnp.max(jnp.abs(
            model.decision_function(x) - x @ res.w))) == 0.0

    def test_cascade_route(self):
        spec = kf.KernelSpec("rbf", 0.5)
        x, y = _blobs(M=256)
        res = baselines._cascade_solve(spec, x, y, PARAMS, levels=2,
                                       key=jax.random.PRNGKey(0))
        want = odm.decision_function(spec, res.x_sv, res.y_sv, res.alpha, x)
        model = serve.from_cascade(spec, res)
        assert _rel_gap(model.decision_function(x), want) < 1e-5
        pred = baselines.cascade_predict(spec, res, x)
        assert float(odm.accuracy(y, pred)) > 0.9


# ---------------------------------------------------------------------------
# compression: pruning + Nyström, and the checkpoint round trip
# ---------------------------------------------------------------------------

class TestCompression:
    def _fit(self, x, y, spec, lam=10.0):
        params = odm.ODMParams(lam=lam, theta=0.1, ups=0.5)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        res = sodm._solve(spec, x, y, params, cfg, jax.random.PRNGKey(2))
        return res, serve.from_sodm(spec, res, x, y)

    def test_pruned_and_nystrom_accuracy_synthetic(self):
        x, y = _blobs(M=256)
        spec = kf.KernelSpec("rbf", 0.5)
        res, exact = self._fit(x, y, spec)
        acc0 = float(odm.accuracy(y, exact.predict(x)))
        pruned = serve.from_sodm(spec, res, x, y, prune_tol=1e-4)
        assert pruned.n_sv <= exact.n_sv
        assert acc0 - float(odm.accuracy(y, pruned.predict(x))) <= 0.005
        # lossy pruning must report the decision gap it introduced
        assert pruned.gap >= 0.0
        if pruned.n_sv < exact.n_sv:
            assert pruned.gap > 0.0
        comp = serve.compress(exact, max(16, exact.n_sv // 4))
        assert comp.compression == "nystrom"
        assert comp.n_sv <= max(16, exact.n_sv // 4)
        assert acc0 - float(odm.accuracy(y, comp.predict(x))) <= 0.005

    def test_compression_accuracy_svmguide1(self):
        ds = synthetic.load("svmguide1", scale=0.05)
        M = ds.x_train.shape[0] - ds.x_train.shape[0] % 8
        x, y = ds.x_train[:M], ds.y_train[:M]
        spec = kf.KernelSpec("rbf", 2.0)
        res, exact = self._fit(x, y, spec)
        acc0 = float(odm.accuracy(ds.y_test, exact.predict(ds.x_test)))
        assert acc0 > 0.85, acc0
        for m in (serve.from_sodm(spec, res, x, y, prune_tol=1e-4),
                  serve.compress(exact, max(16, exact.n_sv // 4),
                                 target=0.05)):
            acc = float(odm.accuracy(ds.y_test, m.predict(ds.x_test)))
            assert acc0 - acc <= 0.005, (m.compression, acc0, acc)

    def test_target_grows_budget(self):
        x, y = _blobs(M=256)
        spec = kf.KernelSpec("rbf", 0.5)
        _, exact = self._fit(x, y, spec)
        loose = serve.compress(exact, 8, target=None)
        tight = serve.compress(exact, 8, target=loose.gap / 4)
        assert tight.n_sv >= loose.n_sv
        assert tight.compression in ("nystrom", exact.compression)

    def test_save_load_roundtrip_exact(self, tmp_path):
        x, y = _blobs()
        for spec in (kf.KernelSpec("rbf", 0.5), kf.KernelSpec("linear")):
            _, model = self._fit(x, y, spec)
            model.save(str(tmp_path / spec.name))
            back = serve.load_model(str(tmp_path / spec.name))
            assert back.compression == model.compression
            assert back.n_train == model.n_train
            a = model.decision_function(x)
            b = back.decision_function(x)
            assert float(jnp.max(jnp.abs(a - b))) == 0.0   # bit-exact
            assert dataclasses.asdict(back.spec) == \
                dataclasses.asdict(model.spec)


# ---------------------------------------------------------------------------
# compile-once predict (the per-call permutation-gather regression)
# ---------------------------------------------------------------------------

class TestPredictCompileOnce:
    def test_gather_runs_once_across_predict_calls(self):
        x, y = _blobs()
        spec = kf.KernelSpec("rbf", 0.5)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(3))
        before = sodm.perm_gather_count()
        p1 = sodm.predict(spec, res, x, y, x[:32])
        p2 = sodm.predict(spec, res, x, y, x[32:64])
        p3 = sodm.predict(spec, res, x, y, x)
        assert sodm.perm_gather_count() - before == 1
        del p1, p2, p3

    def test_fit_seeds_the_predict_cache(self):
        x, y = _blobs()
        spec = kf.KernelSpec("rbf", 0.5)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        before = sodm.perm_gather_count()
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(4))
        model = sodm._cached_model(spec, res, x, y)
        sodm.predict(spec, res, x, y, x[:16])
        assert sodm.perm_gather_count() - before == 1
        assert model.n_train == x.shape[0]

    def test_different_perm_misses_the_cache(self):
        """Same alpha object, different permutation => different model
        (a cache hit here would score with stale SV gathers)."""
        x, y = _blobs()
        spec = kf.KernelSpec("rbf", 0.5)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(8))
        sodm.predict(spec, res, x, y, x[:8])
        before = sodm.perm_gather_count()
        res2 = res._replace(perm=jnp.flip(res.perm))
        sodm.predict(spec, res2, x, y, x[:8])
        assert sodm.perm_gather_count() - before == 1   # recompiled

    def test_score_path_jaxpr_has_no_gather(self):
        """The per-call scoring trace must not permute/gather the training
        set — the compile step did that once."""
        x, y = _blobs()
        spec = kf.KernelSpec("rbf", 0.5)
        cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                              max_sweeps=300)
        res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(5))
        model = serve.from_sodm(spec, res, x, y)
        jaxpr = jax.make_jaxpr(
            lambda xt: model.decision_function(xt))(x[:32])
        assert "gather" not in str(jaxpr)


# ---------------------------------------------------------------------------
# microbatching server
# ---------------------------------------------------------------------------

def _small_model(seed=0):
    x, y = _blobs(M=128, seed=seed)
    spec = kf.KernelSpec("rbf", 0.5)
    cfg = sodm.SODMConfig(p=2, levels=2, n_landmarks=4, tol=1e-5,
                          max_sweeps=300)
    res = sodm._solve(spec, x, y, PARAMS, cfg, jax.random.PRNGKey(6))
    return serve.from_sodm(spec, res, x, y), x


class TestMicrobatchScorer:
    def test_bucketed_scoring_matches_direct(self):
        model, x = _small_model()
        scorer = serve.MicrobatchScorer(model, max_batch=32)
        for B in (1, 3, 7, 17, 32, 77, 128):    # 77/128 exercise chunking
            want = model.decision_function(x[:B])
            got = scorer.score(x[:B])
            assert got.shape == (B,)
            assert _rel_gap(got, want) < 1e-6, B

    def test_jit_cache_bounded_by_bucket_ladder(self):
        model, x = _small_model()
        scorer = serve.MicrobatchScorer(model, max_batch=32)
        for B in range(1, 33):
            scorer.score(x[:B])
        assert scorer.compiles <= len(scorer.buckets)
        assert scorer.buckets == (1, 2, 4, 8, 16, 32)

    def test_empty_batch(self):
        model, x = _small_model()
        scorer = serve.MicrobatchScorer(model, max_batch=32)
        out = scorer.score(x[:0])
        assert out.shape == (0,)


class TestBatcher:
    def test_deadline_flush(self):
        model, x = _small_model()
        b = serve.Batcher(serve.MicrobatchScorer(model, max_batch=32),
                          max_batch=4, max_wait=1e-3)
        for i in range(3):
            b.submit(x[i], now=0.0)
        assert not b.ready(0.0005)              # under deadline, under size
        assert b.poll(0.0005) == []
        done = b.poll(0.0015)                   # oldest past the deadline
        assert [r.rid for r in done] == [0, 1, 2]
        assert b.batches == [3]

    def test_full_batch_flushes_immediately(self):
        model, x = _small_model()
        b = serve.Batcher(serve.MicrobatchScorer(model, max_batch=32),
                          max_batch=4, max_wait=10.0)
        for i in range(5):
            b.submit(x[i], now=0.0)
        done = b.poll(0.0)                      # size-triggered, no wait
        assert len(done) == 4 and len(b._pending) == 1

    def test_stream_scores_match_direct(self):
        model, x = _small_model()
        scorer = serve.MicrobatchScorer(model, max_batch=32)
        b = serve.Batcher(scorer, max_batch=8, max_wait=1e-3)
        n = 40
        stats = serve.serve_stream(
            b, ((i * 1e-4, x[i % x.shape[0]]) for i in range(n)))
        assert len(stats["results"]) == n
        want = np.asarray(model.decision_function(x[:x.shape[0]]))
        got = {r.rid: r.score for r in stats["results"]}
        for i in range(n):
            assert abs(got[i] - float(want[i % x.shape[0]])) < 1e-5
        assert stats["mean_batch"] > 1.0        # batching actually happened

    def test_stream_percentiles_nearest_rank(self):
        """PR 9 regression: p50/p95/p99 are exact nearest-rank over the
        latency sample, not the old lat[n // 2] indexing."""
        from repro import observe
        model, x = _small_model()
        b = serve.Batcher(serve.MicrobatchScorer(model, max_batch=32),
                          max_batch=8, max_wait=1e-3)
        stats = serve.serve_stream(
            b, ((i * 1e-4, x[i % x.shape[0]]) for i in range(40)))
        lat = stats["latencies"]
        assert stats["p50"] == observe.percentile(lat, 50)
        assert stats["p95"] == observe.percentile(lat, 95)
        assert stats["p99"] == observe.percentile(lat, 99)
        assert stats["p50"] <= stats["p95"] <= stats["p99"] <= max(lat)

    def test_request_batch_contains_score_span(self):
        """PR 9 acceptance: a traced serve replay emits nested
        serve.request_batch -> serve.score spans, and the metrics
        registry sees every request's latency."""
        from repro import observe
        model, x = _small_model()
        reg = observe.MetricsRegistry()
        b = serve.Batcher(
            serve.MicrobatchScorer(model, max_batch=32, metrics=reg),
            max_batch=8, max_wait=1e-3, metrics=reg)
        rec = observe.SpanRecorder()
        with observe.install(rec):
            serve.serve_stream(
                b, ((i * 1e-4, x[i % x.shape[0]]) for i in range(24)))
        outer = rec.spans("serve.request_batch")
        inner = rec.spans("serve.score")
        assert outer and len(inner) >= len(outer)
        for s in inner:         # every score sits inside some batch span
            assert any(o["ts"] <= s["ts"] and
                       s["ts"] + s["dur"] <= o["ts"] + o["dur"]
                       for o in outer)
        snap = reg.snapshot()
        assert snap["serve.request.latency_s.count"] == 24
        assert snap["serve.requests.count"] == 24
        assert snap["serve.batches.count"] == len(outer)
        assert snap["serve.queue_depth.max"] >= 1


class TestShardedScoring:
    def test_single_device_mesh_matches(self):
        from repro.launch.mesh import make_host_mesh
        model, x = _small_model()
        mesh = make_host_mesh((1,), ("data",))
        got = serve.score_sharded(model, x[:48], mesh)
        want = model.decision_function(x[:48])
        assert _rel_gap(got, want) < 1e-6

    def test_repeat_calls_share_one_trace(self):
        """score_sharded must not rebuild shard_map/jit per call."""
        from repro.launch.mesh import make_host_mesh
        from repro.serve import server as server_mod
        model, x = _small_model()
        mesh = make_host_mesh((1,), ("data",))
        serve.score_sharded(model, x[:48], mesh)
        info = server_mod._sharded_scorer.cache_info()
        serve.score_sharded(model, x[:48], mesh)
        serve.score_sharded(model, x[:48], mesh)
        after = server_mod._sharded_scorer.cache_info()
        assert after.misses == info.misses      # no new builder
        assert after.hits >= info.hits + 2
