"""Out-of-core streaming data plane (ISSUE 10).

Four batteries:

* sources/loader — protocol conformance, file-backed round trips,
  synthetic determinism, prefetch accounting, label policing, and the
  load-bearing invariant that slab contents are BITWISE independent of
  how the source is sharded (slab boundaries are global row indices);
* one-pass partitioning — reservoir >= M degenerates to the stream, so
  the sketched Eqn. 8 landmark set exactly matches dense
  ``select_landmarks``; ``StreamingAssigner`` strata match dense
  ``assign_strata`` and its round-robin partition labels are
  layout-invariant;
* streaming fits — dsvrg and cascade streaming results are bitwise
  invariant to re-sharding, agree with the identically-ordered resident
  solve, and the end-to-end fit stays under the dataset's byte size
  (the accountant's peak is the proof);
* chaos (``chaos`` marker) — a mid-stream kill resumes through the
  route's resume manager bitwise, and the resumed cascade never
  re-reads a completed shard.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ODMEstimator, ProblemSpec
from repro.core import baselines, kernel_fns as kf, odm, partition, sodm
from repro.core.dsvrg import DSVRGConfig
from repro.data import streaming as ds
from repro.distributed import resume as resume_mod
from repro.distributed.faults import FaultPlan, Preemption
from repro.observe import MetricsRegistry

KEY = jax.random.PRNGKey(0)


def _data(M=256, d=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(M, d)).astype(np.float32)
    y = np.where(rng.random(M) < 0.5, -1.0, 1.0).astype(np.float32)
    return x, y


def _layouts(x, y, tmp_path):
    """The same rows presented four ways (and four shard geometries)."""
    return [
        ds.ArraySource(x, y, shard_rows=32),
        ds.ArraySource(x, y, shard_rows=48),     # straddles slab edges
        ds.NpyShardSource.write(str(tmp_path / "npy"), x, y, shard_rows=64),
        _raw_source(x, y, tmp_path / "raw", shard_rows=80),
    ]


def _raw_source(x, y, directory, shard_rows):
    os.makedirs(directory, exist_ok=True)
    pairs = []
    for i, lo in enumerate(range(0, x.shape[0], shard_rows)):
        xp = str(directory / f"{i}_x.bin")
        yp = str(directory / f"{i}_y.bin")
        x[lo:lo + shard_rows].tofile(xp)
        y[lo:lo + shard_rows].tofile(yp)
        pairs.append((xp, yp))
    return ds.RawBinarySource(pairs, n_features=x.shape[1])


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

class TestSources:
    def test_protocol_and_duck_check(self, tmp_path):
        x, y = _data(64)
        for src in _layouts(x, y, tmp_path):
            assert isinstance(src, ds.ShardedSource)
            assert ds.is_source(src)
        assert not ds.is_source(jnp.asarray(x))
        assert not ds.is_source(x)

    def test_every_layout_round_trips(self, tmp_path):
        x, y = _data(192, 5)
        for src in _layouts(x, y, tmp_path):
            assert src.n_rows == 192 and src.n_features == 5
            assert sum(src.shard_sizes()) == 192
            xm, ym = ds.materialize(src)
            np.testing.assert_array_equal(xm, x)
            np.testing.assert_array_equal(ym, y)
            assert src.total_bytes == 192 * 6 * 4

    def test_synthetic_pure_function_of_seed_and_shard(self):
        src = ds.SyntheticSource(1000, 8, shard_rows=256, seed=3)
        a = src.read_shard(2)
        b = src.read_shard(2)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert set(np.unique(a[1])) <= {-1.0, 1.0}
        # a different seed is different data
        other = ds.SyntheticSource(1000, 8, shard_rows=256, seed=4)
        assert not np.array_equal(other.read_shard(2)[0], a[0])
        # labels are learnable: the class means are separated by
        # 2 * noise * sep along the class direction (by construction)
        xs, ys = ds.materialize(src)
        mu = xs[ys > 0].mean(0) - xs[ys < 0].mean(0)
        assert float(np.linalg.norm(mu)) > 0.2

    def test_read_counters_track_reads(self):
        x, y = _data(96)
        src = ds.ArraySource(x, y, shard_rows=32)
        assert src.reads == [0, 0, 0]
        src.read_shard(1)
        src.read_shard(1)
        assert src.reads == [0, 2, 0]

    def test_validate_source(self):
        x, y = _data(64)
        spec = ProblemSpec()
        spec.validate_source(ds.ArraySource(x, y, shard_rows=16))

        class Hollow:
            n_rows, n_features = 0, 4
            def shard_sizes(self):
                return ()
            def read_shard(self, i):
                raise AssertionError

        with pytest.raises(ValueError, match="empty"):
            spec.validate_source(Hollow())


# ---------------------------------------------------------------------------
# loader
# ---------------------------------------------------------------------------

class TestLoader:
    def test_prefetch_yields_every_shard_in_order(self):
        x, y = _data(160)
        src = ds.ArraySource(x, y, shard_rows=32)
        mets = MetricsRegistry()
        got = list(ds.PrefetchLoader(src, depth=2, metrics=mets))
        assert [i for i, *_ in got] == [0, 1, 2, 3, 4]
        np.testing.assert_array_equal(np.concatenate([g[1] for g in got]), x)
        assert src.reads == [1] * 5
        snap = mets.snapshot()
        assert snap["data.rows.count"] == 160
        assert snap["data.shard.read_s.count"] == 5
        assert snap["data.prefetch.depth.max"] <= 2

    def test_slabs_bitwise_invariant_to_sharding(self, tmp_path):
        x, y = _data(200, 4)
        ref = None
        for src in _layouts(x, y, tmp_path):
            slabs = [(np.asarray(s.x).copy(), np.asarray(s.y).copy(),
                      s.start, s.n_valid)
                     for s in ds.iter_slabs(src, 48)]
            if ref is None:
                ref = slabs
                # tail slab is zero-padded past n_valid
                assert slabs[-1][3] == 200 - 48 * 4
                assert not slabs[-1][0][slabs[-1][3]:].any()
                continue
            for (xa, ya, sa, na), (xb, yb, sb, nb) in zip(ref, slabs,
                                                          strict=True):
                np.testing.assert_array_equal(xa, xb)
                np.testing.assert_array_equal(ya, yb)
                assert (sa, na) == (sb, nb)

    def test_start_row_skips_whole_shards_unread(self):
        x, y = _data(256)
        src = ds.ArraySource(x, y, shard_rows=32)
        slabs = list(ds.iter_slabs(src, 64, start_row=128))
        assert [s.start for s in slabs] == [128, 192]
        assert src.reads[:4] == [0, 0, 0, 0]     # skipped without reading
        np.testing.assert_array_equal(np.asarray(slabs[0].x), x[128:192])
        with pytest.raises(ValueError, match="multiple"):
            next(iter(ds.iter_slabs(src, 64, start_row=10)))

    def test_slab_arrays_do_not_alias_the_carry_buffer(self):
        # jnp.asarray zero-copies on CPU: if the loader reused its carry
        # buffer across yields, consumers' arrays would be corrupted
        x, y = _data(128)
        src = ds.ArraySource(x, y, shard_rows=32)
        kept = [s.x for s in ds.iter_slabs(src, 32)]
        for i, xs in enumerate(kept):
            np.testing.assert_array_equal(np.asarray(xs), x[32 * i:32 * (i + 1)])

    def test_labels_policed_per_shard(self):
        x, y = _data(64)
        y[40] = 0.5
        src = ds.ArraySource(x, y, shard_rows=32)
        with pytest.raises(ValueError, match="labels"):
            list(ds.iter_slabs(src, 32))

    def test_accountant_peak_bounded(self):
        x, y = _data(512, 8)
        src = ds.ArraySource(x, y, shard_rows=32)
        acct = ds.ByteAccountant()
        for _ in ds.iter_slabs(src, 64, depth=2, accountant=acct):
            pass
        assert 0 < acct.peak < src.total_bytes
        assert acct.current == 0                  # everything released
        with pytest.raises(RuntimeError, match="released more"):
            acct.release(1)

    def test_prefetch_kill_and_delay(self):
        x, y = _data(96)
        plan = FaultPlan(sleeper=None).delay_shard_read(1, 0.25) \
                                      .kill("data.prefetch", shard=2)
        src = ds.ArraySource(x, y, shard_rows=32)
        seen = []
        with pytest.raises(Preemption) as ei:
            for i, *_ in ds.PrefetchLoader(src, depth=1, faults=plan,
                                           executor=ds.SerialExecutor()):
                seen.append(i)
        assert ei.value.info == {"shard": 2}
        assert seen == [0, 1]
        assert ("delay", "data.prefetch", {"shard": 1}) in plan.fired


# ---------------------------------------------------------------------------
# one-pass partitioning (Eqn. 7 / Eqn. 8)
# ---------------------------------------------------------------------------

class TestStreamingPlan:
    SPEC = kf.KernelSpec(name="rbf", gamma=0.5)

    def test_reservoir_degenerates_to_stream(self):
        x, y = _data(128)
        src = ds.ArraySource(x, y, shard_rows=48)
        np.testing.assert_array_equal(ds.reservoir_sample(src, 128), x)
        np.testing.assert_array_equal(ds.reservoir_sample(src, 500), x)

    def test_reservoir_is_seed_deterministic_and_uniformish(self):
        x, y = _data(2048, 3, seed=5)
        src = ds.ArraySource(x, y, shard_rows=256)
        a = ds.reservoir_sample(src, 64, seed=9)
        b = ds.reservoir_sample(src, 64, seed=9)
        np.testing.assert_array_equal(a, b)
        c = ds.reservoir_sample(src, 64, seed=10)
        assert not np.array_equal(a, c)
        # sampled rows are actual rows of the stream
        matches = (x[None, :, :] == a[:, None, :]).all(-1).any(1)
        assert matches.all()

    def test_sketch_landmarks_exact_when_reservoir_covers(self, tmp_path):
        x, y = _data(160, 5)
        idx = partition.select_landmarks(self.SPEC, jnp.asarray(x), 8)
        dense = jnp.asarray(x)[idx]
        for src in _layouts(x, y, tmp_path):
            z = ds.sketch_landmarks(self.SPEC, src, 8, reservoir=160)
            np.testing.assert_array_equal(np.asarray(z), np.asarray(dense))
        with pytest.raises(ValueError, match="reservoir"):
            ds.sketch_landmarks(self.SPEC, src, 8, reservoir=4)

    def test_streaming_strata_match_dense(self):
        x, y = _data(256, 5)
        xj = jnp.asarray(x)
        idx = partition.select_landmarks(self.SPEC, xj, 6)
        dense = partition.assign_strata(self.SPEC, xj, idx)
        assigner = ds.StreamingAssigner(self.SPEC, xj[idx], n_partitions=4)
        got, _ = assigner.assign(x)
        np.testing.assert_array_equal(got, np.asarray(dense))

    def test_assignment_layout_invariant_and_balanced(self, tmp_path):
        x, y = _data(300, 5)
        src0 = ds.ArraySource(x, y, shard_rows=64)
        plan = ds.streaming_plan(self.SPEC, src0, n_partitions=4,
                                 n_landmarks=6, reservoir=300)
        ref_s, ref_p = plan.assigner.assign(x)     # whole stream at once
        for src in _layouts(x, y, tmp_path):
            assigner = ds.StreamingAssigner(self.SPEC, plan.landmarks, 4)
            ss, ps = [], []
            for _, xs, _ in ds.PrefetchLoader(src):
                s, p = assigner.assign(xs)
                ss.append(s)
                ps.append(p)
            np.testing.assert_array_equal(np.concatenate(ss), ref_s)
            np.testing.assert_array_equal(np.concatenate(ps), ref_p)
        # within every stratum the K partitions differ by at most one row
        for s in np.unique(ref_s):
            counts = np.bincount(ref_p[ref_s == s], minlength=4)
            assert counts.max() - counts.min() <= 1


# ---------------------------------------------------------------------------
# streaming fits
# ---------------------------------------------------------------------------

def _linear_problem():
    return ProblemSpec(kernel=kf.KernelSpec(name="linear"),
                       params=odm.ODMParams(lam=10.0))


def _dsvrg_cfg(**kw):
    kw.setdefault("epochs", 4)
    kw.setdefault("batch", 64)
    kw.setdefault("schedule", "serial")
    kw.setdefault("stream_slab", 128)
    return sodm.SODMConfig(engine="dsvrg", dsvrg=DSVRGConfig(**kw))


def _op_by_op_stream(source, params, cfg):
    """The streamed solve as its slab loop ran before each slab became one
    jitted call: the slab weights, reshapes, ``stats`` / ``inner`` and the
    accumulations each dispatched on their own. Returns (w, history, kkt,
    eta)."""
    from repro.core import dsvrg
    from repro.data.streaming import loader
    from repro.precision import matmul
    M, d = source.n_rows, source.n_features
    b = min(cfg.batch, M)
    R = -(-max(cfg.stream_slab, b) // b) * b
    C = R // b
    stats, inner = dsvrg._make_stream_steps(params, b,
                                            dsvrg._resolve_fused(cfg))
    f32 = jnp.float32

    def weights(n_valid):
        return (jnp.arange(R) < n_valid).astype(f32)

    def anchor_pass(anchor):
        g, loss, sq = jnp.zeros(d, f32), jnp.zeros((), f32), \
            jnp.zeros((), f32)
        for s in loader.iter_slabs(source, R):
            gp, lp, sp = stats(anchor, jnp.asarray(s.x), jnp.asarray(s.y),
                               weights(s.n_valid), M=M)
            g, loss, sq = g + gp, loss + lp, sq + sp
        return g, loss, sq

    w, eta, hist = jnp.zeros(d, f32), None, []
    for e in range(cfg.epochs):
        anchor = w
        g, loss, sq = anchor_pass(anchor)
        if eta is None:
            eta = dsvrg._eta_from_sumsq(sq, params, M).astype(f32)
        if e > 0:
            hist.append(0.5 * matmul(anchor, anchor) + loss)
        h = anchor + g
        for s in loader.iter_slabs(source, R):
            w = inner(w, anchor, h, eta, jnp.asarray(s.x).reshape(C, b, d),
                      jnp.asarray(s.y).reshape(C, b),
                      weights(s.n_valid).reshape(C, b))
    g, loss, _ = anchor_pass(w)
    hist.append(0.5 * matmul(w, w) + loss)
    return w, jnp.stack(hist), jnp.max(jnp.abs(w + g)), eta


class TestDsvrgStreaming:
    def test_bitwise_invariant_to_sharding(self, tmp_path):
        x, y = _data(512, 8, seed=1)
        problem, cfg = _linear_problem(), _dsvrg_cfg()
        ws = []
        for src in _layouts(x, y, tmp_path):
            m, rep = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
                src, key=KEY)
            ws.append((np.asarray(m.w), rep.history, rep.kkt, rep.eta))
        w0, h0, k0, e0 = ws[0]
        for w, h, k, e in ws[1:]:
            np.testing.assert_array_equal(w, w0)
            assert h == h0 and k == k0 and e == e0

    def test_matches_resident_identity_solve(self):
        x, y = _data(512, 8, seed=1)
        problem = _linear_problem()
        cfg = _dsvrg_cfg(n_partitions=1, partition_strategy="identity")
        src = ds.ArraySource(x, y, shard_rows=128)
        m_s, rep_s = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
            src, key=KEY)
        m_m, rep_m = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
            jnp.asarray(x), jnp.asarray(y), KEY)
        # the hinge gradient is piecewise, so the two FP reduction trees
        # can flip individual margin-boundary samples (each worth O(1/M)
        # in a gradient) — agreement is a relative band, not a bitwise
        # pin; bitwise holds streaming-vs-streaming (test above)
        rel = float(jnp.max(jnp.abs(m_s.w - m_m.w))
                    / jnp.linalg.norm(m_m.w))
        assert rel <= 1e-2
        np.testing.assert_allclose(rep_s.eta, rep_m.eta, rtol=1e-5)
        np.testing.assert_allclose(rep_s.history, rep_m.history, rtol=1e-3)
        xt = jnp.asarray(_data(128, 8, seed=9)[0])
        assert float(jnp.mean(m_s.predict(xt) == m_m.predict(xt))) == 1.0

    def test_trace_once_across_refits(self):
        from repro.analysis.invariants import counter
        x, y = _data(256, 8, seed=2)
        problem, cfg = _linear_problem(), _dsvrg_cfg()
        est = ODMEstimator(problem, route="dsvrg", cfg=cfg)
        est.fit(ds.ArraySource(x, y, shard_rows=64), key=KEY)   # warm
        traces = counter("dsvrg.epoch_trace")
        n0 = traces.count
        est.fit(ds.ArraySource(x, y, shard_rows=64), key=KEY)
        assert traces.count == n0

    def test_one_program_call_a_slab_one_trace_a_config(self):
        from repro import observe
        from repro.core import dsvrg
        dsvrg._make_stream_steps.cache_clear()      # fresh slab programs
        x, y = _data(300, 8, seed=2)                # slabs of 128, 128, 44
        est = ODMEstimator(_linear_problem(), route="dsvrg",
                           cfg=_dsvrg_cfg(epochs=2))
        n0 = len(dsvrg._TRACE_EVENTS)
        rec = observe.SpanRecorder()
        with observe.install(rec):
            for _ in range(2):
                est.fit(ds.ArraySource(x, y, shard_rows=64), key=KEY)
        passes = rec.spans("dsvrg.pass")
        assert len(passes) == 2 * (2 * 2 + 1)
        assert [(p["args"]["slabs"], p["args"]["steps"]) for p in passes] \
            == [(3, 3)] * len(passes)
        tags = sorted(e[0] for e in dsvrg._TRACE_EVENTS[n0:])
        assert tags == ["stream.anchor_step", "stream.inner",
                        "stream.inner_step", "stream.stats"]

    @pytest.mark.parametrize("fused", [False, True])
    def test_matches_the_op_by_op_slab_loop(self, fused):
        from repro.core import dsvrg
        # slabs of 128, 128 and 44 rows: the last one's second minibatch
        # of 64 is all padding
        x, y = _data(300, 8, seed=3)
        cfg = DSVRGConfig(epochs=3, batch=64, stream_slab=128, fused=fused)
        params = _linear_problem().params
        res, kkt = dsvrg._solve_stream(
            ds.ArraySource(x, y, shard_rows=96), params, cfg)
        w, hist, kkt_ref, eta = _op_by_op_stream(
            ds.ArraySource(x, y, shard_rows=96), params, cfg)
        np.testing.assert_array_equal(np.asarray(res.w), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(res.history),
                                      np.asarray(hist))
        assert float(kkt) == float(kkt_ref)
        assert float(res.eta) == float(eta)

    def test_planted_direction_reaches_the_streamed_fit(self, monkeypatch):
        """``_make_stream_steps.cache_clear()`` and ``jax.clear_caches()``
        drop every traced slab program, so a ``_direction`` planted after
        them runs in the next streamed fit (what a fault test relies on),
        and clearing again brings the real one back."""
        from repro.core import dsvrg
        x, y = _data(256, 8, seed=4)
        cfg = DSVRGConfig(epochs=2, batch=64, stream_slab=128)
        params = _linear_problem().params

        def fit():
            res, _ = dsvrg._solve_stream(
                ds.ArraySource(x, y, shard_rows=64), params, cfg)
            return np.asarray(res.w)

        def forget():
            dsvrg._make_stream_steps.cache_clear()
            jax.clear_caches()

        real = fit()
        assert np.abs(real).max() > 0
        forget()
        monkeypatch.setattr(dsvrg, "_direction",
                            lambda w, *a, **k: jnp.zeros_like(w))
        try:
            assert not fit().any()        # the chain never left w0 = 0
        finally:
            monkeypatch.undo()
            forget()
        np.testing.assert_array_equal(fit(), real)

    def test_streaming_capability_declared(self):
        from repro.api import registry
        assert "dsvrg" in registry.streaming_routes()
        assert "cascade" in registry.streaming_routes()
        assert "streaming=True" in registry.get("dsvrg").capabilities()


class TestCascadeStreaming:
    PROBLEM = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5),
                          params=odm.ODMParams(lam=50.0))
    CFG = sodm.SODMConfig(levels=3, tol=1e-6, max_sweeps=200)

    def test_bitwise_invariant_to_sharding(self, tmp_path):
        x, y = _data(256, 6)
        xt = jnp.asarray(_data(64, 6, seed=7)[0])
        ref = None
        for src in _layouts(x, y, tmp_path):
            m, rep = ODMEstimator(self.PROBLEM, route="cascade",
                                  cfg=self.CFG).fit(src, key=KEY)
            scores = np.asarray(m.decision_function(xt))
            assert rep.passes == (self.CFG.levels + 1,)
            if ref is None:
                ref = scores
            else:
                np.testing.assert_array_equal(scores, ref)

    def test_matches_dense_identity_cascade(self):
        x, y = _data(256, 6)
        dense = baselines._cascade_solve(
            self.PROBLEM.kernel, jnp.asarray(x), jnp.asarray(y),
            self.PROBLEM.params, levels=3, key=KEY, tol=1e-6,
            max_sweeps=200, perm=jnp.arange(256))
        m_s, _ = ODMEstimator(self.PROBLEM, route="cascade",
                              cfg=self.CFG).fit(
            ds.ArraySource(x, y, shard_rows=64), key=KEY)
        from repro.serve import model as serve_model
        xt = jnp.asarray(_data(64, 6, seed=7)[0])
        f_dense = serve_model.from_cascade(
            self.PROBLEM.kernel, dense).decision_function(xt)
        f_stream = m_s.decision_function(xt)
        assert float(jnp.max(jnp.abs(f_stream - f_dense))) <= 1e-5


# ---------------------------------------------------------------------------
# end-to-end: train past a host-memory budget
# ---------------------------------------------------------------------------

def test_e2e_fit_exceeds_resident_budget():
    """ISSUE 10 acceptance: the dataset never fits in the (accounted)
    resident budget, yet the streamed fit matches the in-memory one."""
    rows, d = 32_768, 16
    src = ds.SyntheticSource(rows, d, shard_rows=2_048, seed=2, sep=1.5)
    budget = src.total_bytes // 4              # the "capped host RAM"
    problem = _linear_problem()
    cfg = _dsvrg_cfg(epochs=2, batch=256, stream_slab=1_024,
                     n_partitions=1, partition_strategy="identity")
    acct = ds.ByteAccountant()
    m_s, rep = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
        src, key=KEY, accountant=acct)
    assert 0 < acct.peak < budget < src.total_bytes
    x, y = ds.materialize(src)
    m_m, _ = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
        jnp.asarray(x), jnp.asarray(y), KEY)
    rel = float(jnp.max(jnp.abs(m_s.w - m_m.w)) / jnp.linalg.norm(m_m.w))
    assert rel <= 1e-2
    agree = float(jnp.mean(m_s.predict(jnp.asarray(x))
                           == m_m.predict(jnp.asarray(x))))
    assert agree >= 0.99
    assert rep.passes[0] == cfg.dsvrg.epochs


# ---------------------------------------------------------------------------
# dispatch stays loud
# ---------------------------------------------------------------------------

class TestDispatch:
    def test_source_plus_y_rejected(self):
        x, y = _data(64)
        src = ds.ArraySource(x, y, shard_rows=32)
        with pytest.raises(ValueError, match="ambiguous"):
            ODMEstimator(_linear_problem()).fit(src, jnp.asarray(y))

    def test_non_streaming_route_rejected(self):
        x, y = _data(64)
        src = ds.ArraySource(x, y, shard_rows=32)
        with pytest.raises(ValueError, match="streaming"):
            ODMEstimator(ProblemSpec(), route="sodm").fit(src, key=KEY)

    def test_mesh_plus_source_rejected(self):
        from repro.api import registry
        with pytest.raises(ValueError, match="SPMD"):
            registry.resolve(ProblemSpec(), M=1024,
                             mesh="fake-mesh", route=None, streaming=True)

    def test_auto_policy_linear_dsvrg_kernel_cascade(self):
        from repro.api import registry
        lin = ProblemSpec(kernel=kf.KernelSpec(name="linear"))
        rbf = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=1.0))
        assert registry.resolve(lin, M=1024, streaming=True).name == "dsvrg"
        assert registry.resolve(rbf, M=1024, streaming=True).name \
            == "cascade"

    def test_loader_knobs_rejected_on_dense_fit(self):
        x, y = _data(64)
        with pytest.raises(ValueError, match="loader"):
            ODMEstimator(_linear_problem(), route="dsvrg").fit(
                jnp.asarray(x), jnp.asarray(y), KEY,
                accountant=ds.ByteAccountant())


# ---------------------------------------------------------------------------
# chaos: mid-stream kills resume without rework
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestStreamingChaos:
    PROBLEM = ProblemSpec(kernel=kf.KernelSpec(name="rbf", gamma=0.5),
                          params=odm.ODMParams(lam=50.0))
    CFG = sodm.SODMConfig(levels=3, tol=1e-6, max_sweeps=200)

    def test_cascade_mid_stream_kill_resumes_without_rereads(
            self, tmp_path):
        x, y = _data(256, 6)
        m_ok, _ = ODMEstimator(self.PROBLEM, route="cascade",
                               cfg=self.CFG).fit(
            ds.NpyShardSource.write(str(tmp_path / "a"), x, y, 32),
            key=KEY)
        src = ds.NpyShardSource.write(str(tmp_path / "b"), x, y, 32)
        est = ODMEstimator(self.PROBLEM, route="cascade", cfg=self.CFG)
        rdir = str(tmp_path / "resume")
        with pytest.raises(Preemption):
            est.fit(src, key=KEY, resume=rdir,
                    faults=FaultPlan().kill_at_shard(5))
        killed_reads = list(src.reads)
        assert killed_reads[:5] == [1] * 5        # leaves 0-4 completed
        m2, _ = est.fit(src, key=KEY, resume=rdir)
        # completed shards are not re-read (prefetched-but-unconsumed
        # ones may be; prefetch is allowed to waste, resume is not)
        assert src.reads[:5] == [1] * 5
        xt = jnp.asarray(_data(64, 6, seed=7)[0])
        np.testing.assert_array_equal(
            np.asarray(m2.decision_function(xt)),
            np.asarray(m_ok.decision_function(xt)))

    def test_dsvrg_stream_kill_at_epoch_resumes_bitwise(self, tmp_path):
        x, y = _data(512, 8, seed=1)
        problem, cfg = _linear_problem(), _dsvrg_cfg()
        src_ok = ds.NpyShardSource.write(str(tmp_path / "a"), x, y, 96)
        m_ok, rep_ok = ODMEstimator(problem, route="dsvrg", cfg=cfg).fit(
            src_ok, key=KEY)
        src = ds.NpyShardSource.write(str(tmp_path / "b"), x, y, 96)
        est = ODMEstimator(problem, route="dsvrg", cfg=cfg)
        rdir = str(tmp_path / "resume")
        with pytest.raises(Preemption):
            est.fit(src, key=KEY, resume=rdir,
                    faults=FaultPlan().kill_at_epoch(2))
        m2, rep2 = est.fit(src, key=KEY, resume=rdir)
        np.testing.assert_array_equal(np.asarray(m2.w), np.asarray(m_ok.w))
        assert rep2.history == rep_ok.history

    def test_stream_and_dense_checkpoints_do_not_splice(self, tmp_path):
        x, y = _data(256, 6)
        src = ds.ArraySource(x, y, shard_rows=32)
        est = ODMEstimator(self.PROBLEM, route="cascade", cfg=self.CFG)
        rdir = str(tmp_path / "resume")
        est.fit(src, key=KEY, resume=rdir)        # leaves stream ckpts
        prov = resume_mod.provenance_source(self.PROBLEM.kernel,
                                            self.PROBLEM.params, self.CFG,
                                            src, KEY)
        mgr = resume_mod.CascadeResumeManager(
            resume_mod.ResumeConfig(rdir), prov)
        with pytest.raises(resume_mod.ProvenanceError, match="stream"):
            mgr.restore()

    def test_foreign_source_provenance_rejected(self, tmp_path):
        x, y = _data(256, 6)
        est = ODMEstimator(self.PROBLEM, route="cascade", cfg=self.CFG)
        rdir = str(tmp_path / "resume")
        est.fit(ds.ArraySource(x, y, shard_rows=32), key=KEY, resume=rdir)
        x2, y2 = _data(256, 6, seed=42)
        with pytest.raises(resume_mod.ProvenanceError, match="different"):
            est.fit(ds.ArraySource(x2, y2, shard_rows=32), key=KEY,
                    resume=rdir)
