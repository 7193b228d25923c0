"""Telemetry tests: spans, instruments, trackers, trend gate.

The span tests cover the recorder's parent links (one thread, and work
bound to another thread), attributes set at a span's end, the profiler
annotation and the compile listener (both only once a recorder is
installed), and the program spans of a streamed and a cascade fit at a
tiny size on the CPU. The rest is host-only; the training/serving
integration of the same pieces is also pinned by ``analysis.invariants``
(components.observe.zero_cost_off) and the bench smoke tier. Covers:

* the shared nearest-rank percentile over known distributions (the
  ``lat[n // 2]`` off-by-one regression);
* JsonlTracker's persistent handle + torn-tail tolerance;
* ``read_jsonl`` edge cases (empty / only-torn / interleaved writers);
* Tracker runtime-protocol conformance for every backend, the draining
  MetricsRegistry included;
* the bench gate failing on an injected 10x slowdown and passing on an
  unchanged run.
"""
import importlib.util
import json
import os
import threading

import pytest

from repro import observe
from repro.observe import trend


# ---------------------------------------------------------------------------
# percentile (satellite: serve_stream off-by-one fix)
# ---------------------------------------------------------------------------

class TestPercentile:
    def test_known_distribution_1_to_100(self):
        vals = list(range(1, 101))
        assert observe.percentile(vals, 50) == 50
        assert observe.percentile(vals, 95) == 95
        assert observe.percentile(vals, 99) == 99
        assert observe.percentile(vals, 0) == 1
        assert observe.percentile(vals, 100) == 100

    def test_even_small_n_median(self):
        # THE regression: lat[n // 2] returned 3 (the 75th percentile)
        # for n=4; nearest-rank p50 is the 2nd order statistic
        assert observe.percentile([1, 2, 3, 4], 50) == 2
        assert observe.percentile([1, 2, 3, 4], 95) == 4
        assert observe.percentile([10, 20], 50) == 10

    def test_single_element_and_unsorted(self):
        assert observe.percentile([7.0], 50) == 7.0
        assert observe.percentile([7.0], 99) == 7.0
        assert observe.percentile([3, 1, 2], 50) == 2
        sorted_in = [1, 2, 3]
        observe.percentile(sorted_in, 95)
        assert sorted_in == [1, 2, 3]      # never mutates the input

    def test_nearest_rank_exactness(self):
        # n=10: p90 is exactly the 9th order statistic, p91 the 10th
        vals = list(range(10))
        assert observe.percentile(vals, 90) == 8
        assert observe.percentile(vals, 91) == 9

    def test_errors(self):
        with pytest.raises(ValueError):
            observe.percentile([], 50)
        with pytest.raises(ValueError):
            observe.percentile([1.0], 101)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_off_path_is_shared_noop(self):
        assert observe.current_recorder() is None
        assert observe.span("a", x=1) is observe.span("b")
        with observe.span("c") as sp:
            sp.set(passes=3)                  # accepted, recorded nowhere
        assert observe.bind(len) is len

    def test_record_and_nesting_by_containment(self):
        rec = observe.SpanRecorder()
        with observe.install(rec):
            with observe.span("outer", level=2):
                with observe.span("inner"):
                    pass
        outer, = rec.spans("outer")
        inner, = rec.spans("inner")
        assert outer["ph"] == inner["ph"] == "X"
        assert outer["args"] == {"id": outer["args"]["id"], "parent": None,
                                 "level": 2}
        assert inner["args"]["parent"] == outer["args"]["id"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["tid"] == inner["tid"]

    def test_install_restores_previous(self):
        r1, r2 = observe.SpanRecorder(), observe.SpanRecorder()
        with observe.install(r1):
            with observe.install(r2):
                with observe.span("in2"):
                    pass
            with observe.span("in1"):
                pass
        assert observe.current_recorder() is None
        assert len(r2.spans("in2")) == 1 and not r2.spans("in1")
        assert len(r1.spans("in1")) == 1 and not r1.spans("in2")

    def test_worker_threads_record_with_own_tid(self):
        rec = observe.SpanRecorder()

        def work():
            with observe.span("worker"):
                pass

        with observe.install(rec):
            t = threading.Thread(target=work)
            t.start()
            t.join()
            with observe.span("main"):
                pass
        tids = {e["tid"] for e in rec.events()}
        assert len(tids) == 2

    def test_span_recorded_even_when_body_raises(self):
        rec = observe.SpanRecorder()
        with observe.install(rec):
            with pytest.raises(RuntimeError):
                with observe.span("boom"):
                    raise RuntimeError
        assert len(rec.spans("boom")) == 1

    def test_export_valid_chrome_trace(self, tmp_path):
        rec = observe.SpanRecorder()
        with observe.install(rec), observe.span("fit", route="sodm"):
            pass
        path = rec.export(tmp_path / "deep" / "trace.json")
        doc = json.loads(open(path).read())
        assert doc["displayTimeUnit"] == "ms"
        ev, = doc["traceEvents"]
        assert set(ev) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert not list((tmp_path / "deep").glob("*.tmp"))

    def test_trace_ctx_none_is_noop(self):
        with observe.trace_ctx(None) as rec:
            assert rec is None
            assert observe.current_recorder() is None

    def test_trace_ctx_exports_even_on_raise(self, tmp_path):
        with pytest.raises(RuntimeError):
            with observe.trace_ctx(tmp_path):
                with observe.span("partial"):
                    pass
                raise RuntimeError
        doc = json.loads((tmp_path / "trace.json").read_text())
        assert [e["name"] for e in doc["traceEvents"]] == ["partial"]
        assert observe.current_recorder() is None

    def test_nonjson_attrs_coerced(self):
        rec = observe.SpanRecorder()
        with observe.install(rec), observe.span("s", obj=object(), f=1.5):
            pass
        args = rec.events()[0]["args"]
        json.dumps(args)                     # must be serialisable
        assert args["f"] == 1.5

    def test_set_attributes_at_exit_reach_the_export(self, tmp_path):
        rec = observe.SpanRecorder()
        with observe.install(rec):
            with observe.span("cascade.level", level=1) as sp:
                sp.set(passes=7)
                sp.set(passes=8, kkt=1e-5)
        path = rec.export(tmp_path / "trace.json")
        ev, = json.loads(open(path).read())["traceEvents"]
        assert ev["args"]["level"] == 1
        assert ev["args"]["passes"] == 8 and ev["args"]["kkt"] == 1e-5

    def test_bound_work_names_the_submitters_span(self):
        from concurrent.futures import ThreadPoolExecutor
        from repro.data.streaming.loader import SerialExecutor

        def work():
            with observe.span("worker"):
                pass

        rec = observe.SpanRecorder()
        with observe.install(rec), ThreadPoolExecutor(1) as pool:
            with observe.span("asker"):
                pool.submit(observe.bind(work)).result(timeout=30)
                SerialExecutor().submit(observe.bind(work)).result()
            pool.submit(observe.bind(work)).result(timeout=30)  # no span
        asker, = rec.spans("asker")
        threaded, inline, orphan = rec.spans("worker")
        assert threaded["args"]["parent"] == asker["args"]["id"]
        assert threaded["tid"] != asker["tid"]
        assert inline["args"]["parent"] == asker["args"]["id"]
        assert orphan["args"]["parent"] is None
        ids = [e["args"]["id"] for e in rec.events()]
        assert len(set(ids)) == len(ids)


class _FakeAnnotation:
    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.entered.append(self.name)

    def __exit__(self, *exc):
        return False


def test_annotation_only_under_a_recorder(monkeypatch):
    from repro.observe import spans
    with observe.install(observe.SpanRecorder()):
        pass                                   # arms the module once
    _FakeAnnotation.entered = []
    monkeypatch.setattr(spans, "_ANNOTATION", _FakeAnnotation)
    with observe.span("off"):
        pass
    assert _FakeAnnotation.entered == []
    with observe.install(observe.SpanRecorder()):
        with observe.span("on"):
            pass
    assert _FakeAnnotation.entered == ["on"]


def test_spans_on_the_profilers_clock(tmp_path):
    """A profiler trace taken while a recorder is installed holds the
    program's span on a host plane of its ``.xplane.pb``."""
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with observe.install(observe.SpanRecorder()):
            with observe.span("probe.annotated"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert "probe.annotated" in names


_LISTENER_PROBE = r"""
import json, jax
from jax._src import monitoring
from repro import observe
from repro.observe import spans

def registered():
    return monitoring.get_event_duration_listeners().count(spans._on_duration)

out = {"before": registered()}
jax.jit(lambda a: a * 2)(jax.numpy.ones(3))     # compiles, no recorder
out["after_bare_compile"] = registered()
rec = observe.SpanRecorder()
with observe.install(rec):
    with observe.span("outer"):
        jax.jit(lambda a: a + 3)(jax.numpy.ones(5))
out["after_install"] = registered()
n_on = len(rec.events())
jax.jit(lambda a: a - 4)(jax.numpy.ones(7))     # recorder gone
out["recorded_after_uninstall"] = len(rec.events()) - n_on
with observe.install(observe.SpanRecorder()):
    pass
out["after_second_install"] = registered()
outer, = rec.spans("outer")
out["compiles"] = sorted({e["name"] for e in rec.events()
                          if e["name"].startswith("compile.")})
out["parents_ok"] = all(
    e["args"]["parent"] == outer["args"]["id"]
    and outer["ts"] <= e["ts"] + 1
    and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1
    for e in rec.events() if e["name"].startswith("compile."))
print(json.dumps(out))
"""


def test_compile_listener_registered_by_the_first_install_only():
    """In a fresh process: no listener until a recorder is installed, one
    after, however many installs; compile spans only while a recorder is
    installed, inside the span that compiled."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _LISTENER_PROBE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["before"] == out["after_bare_compile"] == 0
    assert out["after_install"] == out["after_second_install"] == 1
    assert out["recorded_after_uninstall"] == 0
    assert out["compiles"] == ["compile.backend", "compile.lower",
                               "compile.trace"]
    assert out["parents_ok"]


# ---------------------------------------------------------------------------
# program spans of a tiny streamed fit and a tiny cascade fit (CPU)
# ---------------------------------------------------------------------------

_STREAM_M, _STREAM_D, _EPOCHS, _SLAB = 5000, 6, 2, 512


def _stream_source(kind, tmp_path):
    import numpy as np
    from repro.data import streaming
    rng = np.random.default_rng(0)
    x = rng.normal(size=(_STREAM_M, _STREAM_D)).astype(np.float32)
    y = np.where(rng.random(_STREAM_M) < 0.5, -1.0, 1.0).astype(np.float32)
    if kind == "array":
        return streaming.ArraySource(x, y, shard_rows=1100)
    pairs = []
    for s, lo in enumerate(range(0, _STREAM_M, 1100)):
        xp, yp = tmp_path / f"x{s}.npy", tmp_path / f"y{s}.npy"
        np.save(xp, x[lo:lo + 1100])
        np.save(yp, y[lo:lo + 1100])
        pairs.append((str(xp), str(yp)))
    return streaming.NpyShardSource(pairs)


def _stream_estimator():
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core.dsvrg import DSVRGConfig
    from repro.core.sodm import SODMConfig
    return ODMEstimator(
        ProblemSpec.create("linear", lam=1.0, theta=0.1, ups=0.5),
        route="dsvrg", cfg=SODMConfig(engine="dsvrg", dsvrg=DSVRGConfig(
            epochs=_EPOCHS, batch=64, stream_slab=_SLAB)))


class _CountingClock:
    """Stands in for the ``time`` module of ``repro.core.dsvrg``."""

    def __init__(self):
        import time
        self.calls = 0
        self._time = time

    def perf_counter(self):
        self.calls += 1
        return self._time.perf_counter()


@pytest.mark.parametrize("kind", ["array", "npy"])
def test_streamed_fit_pass_spans(kind, tmp_path, monkeypatch):
    from repro.core import dsvrg
    source = _stream_source(kind, tmp_path)
    est = _stream_estimator()
    clock = _CountingClock()
    monkeypatch.setattr(dsvrg, "time", clock)
    model_bare, _ = est.fit(source)
    assert clock.calls == 0                  # untraced: no per-slab clock

    rec = observe.SpanRecorder()
    with observe.install(rec):
        model, _ = est.fit(source)
    assert clock.calls > 0
    assert (model.w == model_bare.w).all()   # tracing changes no number
    passes = rec.spans("dsvrg.pass")
    assert [(p["args"]["kind"], p["args"]["epoch"]) for p in passes] == \
        [("anchor", 0), ("inner", 0), ("anchor", 1), ("inner", 1),
         ("final", 2)]
    assert len(passes) == 2 * _EPOCHS + 1
    route, = rec.spans("route.dsvrg")
    n_slabs = -(-_STREAM_M // _SLAB)
    for p in passes:
        a = p["args"]
        assert a["parent"] == route["args"]["id"]
        assert a["slabs"] == n_slabs and a["rows"] == _STREAM_M
        assert a["h2d_bytes"] == n_slabs * _SLAB * (_STREAM_D + 1) * 4
        assert 0 < a["wait_s"] + a["h2d_s"] + a["dispatch_s"] \
            <= p["dur"] / 1e6
    shards = rec.spans("data.shard")
    assert len(shards) == len(passes) * 5
    pass_ids = {p["args"]["id"] for p in passes}
    assert {s["args"]["parent"] for s in shards} == pass_ids
    by_id = {p["args"]["id"]: p for p in passes}
    for s in shards:                         # each read inside its pass
        p = by_id[s["args"]["parent"]]
        assert p["ts"] <= s["ts"] and \
            s["ts"] + s["dur"] <= p["ts"] + p["dur"]


def test_cascade_fit_spans():
    import numpy as np
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core.sodm import SODMConfig
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 3)).astype(np.float32)
    y = np.where(x[:, 0] > 0, 1.0, -1.0).astype(np.float32)
    est = ODMEstimator(ProblemSpec.create("rbf", gamma=0.5), route="sodm",
                       cfg=SODMConfig(engine="scalar", p=2, levels=2,
                                      n_landmarks=4, max_sweeps=50))
    rec = observe.SpanRecorder()
    with observe.install(rec):
        _, report = est.fit(x, y)
    route, = rec.spans("route.sodm")
    rid = route["args"]["id"]
    part, = rec.spans("sodm.partition")
    assert part["args"]["parent"] == rid and part["args"]["K"] == 4
    levels = rec.spans("cascade.level")
    assert [e["args"]["passes"] for e in levels] == list(report.passes)
    merges = rec.spans("cascade.merge")
    assert len(merges) == len(levels) - 1
    assert all(e["args"]["parent"] == rid for e in levels + merges)
    artifact, = rec.spans("fit.artifact")
    assert artifact["args"]["parent"] == rid
    assert part["ts"] < levels[0]["ts"] < merges[0]["ts"] < levels[1]["ts"]
    assert levels[-1]["ts"] < artifact["ts"]


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

class TestInstruments:
    def test_counter_gauge(self):
        c = observe.Counter("req")
        c.inc(); c.inc(3)
        assert c.snapshot() == {"req.count": 4}
        g = observe.Gauge("depth")
        assert g.snapshot() == {}
        g.set(5); g.set(2); g.set(3)
        assert g.snapshot() == {"depth": 3, "depth.min": 2, "depth.max": 5}

    def test_histogram_exact_percentiles(self):
        h = observe.Histogram("lat")
        for v in range(1, 101):
            h.observe(v)
        snap = h.snapshot()
        assert snap["lat.count"] == 100
        assert snap["lat.p50"] == 50
        assert snap["lat.p95"] == 95
        assert snap["lat.p99"] == 99
        assert snap["lat.min"] == 1 and snap["lat.max"] == 100
        assert snap["lat.mean"] == pytest.approx(50.5)

    def test_histogram_bucket_counts_stay_exact_past_cap(self):
        h = observe.Histogram("x", buckets=(1.0, 10.0), max_samples=64)
        for i in range(1000):
            h.observe(0.5 if i % 2 else 5.0)
        assert h.n == 1000
        assert sum(h.counts) == 1000           # bucket counts never sampled
        assert len(h.samples) <= 64
        assert h.percentile(50) in (0.5, 5.0)  # sampled, still plausible

    def test_registry_get_or_create_and_type_conflict(self):
        m = observe.MetricsRegistry()
        assert m.counter("a") is m.counter("a")
        with pytest.raises(TypeError):
            m.gauge("a")

    def test_registry_log_metrics_observes_numerics_only(self):
        m = observe.MetricsRegistry()
        m.log_metrics(0, {"kkt": 0.5, "route": "sodm", "done": True})
        m.log_metrics(1, {"kkt": 1.5})
        snap = m.snapshot()
        assert snap["kkt.count"] == 2
        assert snap["kkt.p50"] == 0.5
        assert "route.count" not in snap and "done.count" not in snap

    def test_registry_drains_through_any_tracker(self, tmp_path):
        m = observe.MetricsRegistry()
        m.histogram("lat").observe(1.0)
        m.counter("req").inc(2)
        mem = observe.InMemoryTracker()
        path = tmp_path / "drain.jsonl"
        with observe.JsonlTracker(path) as jt:
            snap = m.drain(observe.CompositeTracker([mem, jt]), step=7)
        assert mem.steps[0][0] == 7
        assert mem.latest()["req.count"] == 2
        rec, = observe.read_jsonl(path)
        assert rec["step"] == 7 and rec["lat.p99"] == 1.0
        assert snap["lat.count"] == 1

    def test_snapshot_folds_in_invariant_counters(self):
        from repro.analysis import invariants as inv
        inv.counter("observe.test_counter").bump()
        m = observe.MetricsRegistry()
        snap = m.snapshot(include_counters=True)
        assert snap["counter.observe.test_counter.count"] >= 1
        assert "counter.observe.test_counter.count" not in m.snapshot()


# ---------------------------------------------------------------------------
# tracker backends (protocol conformance + jsonl lifecycle)
# ---------------------------------------------------------------------------

class TestTrackerBackends:
    def test_runtime_protocol_conformance(self, tmp_path):
        backends = [
            observe.InMemoryTracker(),
            observe.JsonlTracker(tmp_path / "t.jsonl"),
            observe.CompositeTracker([]),
            observe.MetricsRegistry(),
        ]
        for b in backends:
            assert isinstance(b, observe.Tracker), type(b).__name__
        class Nope:
            pass
        assert not isinstance(Nope(), observe.Tracker)

    def test_jsonl_persistent_handle(self, tmp_path):
        path = tmp_path / "m.jsonl"
        t = observe.JsonlTracker(path)
        assert t._file is None                 # lazy: no file until logged
        t.log_metrics(0, {"a": 1})
        f0 = t._file
        t.log_metrics(1, {"a": 2})
        assert t._file is f0                   # ONE handle across calls
        # every line is already durable before close
        assert [r["a"] for r in observe.read_jsonl(path)] == [1, 2]
        t.close()
        assert t._file is None
        t.log_metrics(2, {"a": 3})             # reopens transparently
        t.close()
        assert len(observe.read_jsonl(path)) == 3

    def test_jsonl_context_manager_closes(self, tmp_path):
        path = tmp_path / "m.jsonl"
        with observe.JsonlTracker(path) as t:
            t.log_metrics(0, {"x": 1.0})
            assert t._file is not None
        assert t._file is None

    def test_jsonl_torn_tail_still_tolerated(self, tmp_path):
        """Regression for the persistent-handle change: a torn final line
        (killed writer) must still be skipped by read_jsonl."""
        path = tmp_path / "m.jsonl"
        t = observe.JsonlTracker(path)
        for i in range(3):
            t.log_metrics(i, {"v": i})
        t.close()
        with open(path, "a") as f:
            f.write('{"step": 99, "v": tor')   # no newline, invalid json
        recs = observe.read_jsonl(path)
        assert [r["step"] for r in recs] == [0, 1, 2]


class TestReadJsonlEdgeCases:
    def test_empty_file(self, tmp_path):
        path = tmp_path / "e.jsonl"
        path.write_text("")
        assert observe.read_jsonl(path) == []

    def test_only_torn_lines(self, tmp_path):
        path = tmp_path / "torn.jsonl"
        path.write_text('{"a": \n{"b"\nnot json at all\n')
        assert observe.read_jsonl(path) == []

    def test_blank_lines_skipped(self, tmp_path):
        path = tmp_path / "b.jsonl"
        path.write_text('\n{"step": 0}\n\n{"step": 1}\n')
        assert [r["step"] for r in observe.read_jsonl(path)] == [0, 1]

    def test_interleaved_writers(self, tmp_path):
        """Two trackers appending to one path: O_APPEND + one write per
        line means whole lines interleave and nothing is lost."""
        path = tmp_path / "shared.jsonl"
        a = observe.JsonlTracker(path)
        b = observe.JsonlTracker(path)
        for i in range(5):
            a.log_metrics(i, {"w": "a"})
            b.log_metrics(i, {"w": "b"})
        a.close(); b.close()
        recs = observe.read_jsonl(path)
        assert len(recs) == 10
        assert {r["w"] for r in recs} == {"a", "b"}
        assert sorted(r["step"] for r in recs if r["w"] == "a") == \
            list(range(5))


# ---------------------------------------------------------------------------
# trend + bench gate
# ---------------------------------------------------------------------------

def _bench_record(name="serve", wall=1.0, peak=1 << 24, rows=3,
                  backend="cpu", device="cpu", metrics=None):
    return {"schema_version": 2, "bench": name, "device_kind": device,
            "backend": backend, "jax_version": "0.0.test",
            "wall_clock_s": wall, "peak_bytes": peak, "rows": rows,
            "lines": ["x"] * rows, "metrics": metrics or {}}


def _write_dir(d, *recs):
    os.makedirs(d, exist_ok=True)
    for r in recs:
        with open(os.path.join(d, f"BENCH_{r['bench']}.json"), "w") as f:
            json.dump(r, f)
    return d


def _gate_main():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


class TestTrendGate:
    def test_identical_run_passes(self, tmp_path):
        base = _write_dir(tmp_path / "base", _bench_record())
        cur = _write_dir(tmp_path / "cur", _bench_record())
        findings = trend.compare_dirs(cur, base)
        assert not any(f.regressed for f in findings)
        assert _gate_main()([str(cur), str(base)]) == 0

    def test_injected_10x_slowdown_fails(self, tmp_path):
        """The ISSUE 9 acceptance criterion: 10x wall-clock must trip the
        gate; the same 10x on different hardware only warns."""
        base = _write_dir(tmp_path / "base", _bench_record(wall=1.0))
        cur = _write_dir(tmp_path / "cur", _bench_record(wall=10.0))
        findings = trend.compare_dirs(cur, base)
        bad = [f for f in findings if f.regressed]
        assert [f.field for f in bad] == ["wall_clock_s"]
        assert _gate_main()([str(cur), str(base)]) == 1

    def test_noise_band_absorbs_small_jitter(self, tmp_path):
        # +60% on a 50ms bench: inside both the 2x band and the absolute
        # floor — the gate must not flake on scheduler noise
        base = _write_dir(tmp_path / "base", _bench_record(wall=0.05))
        cur = _write_dir(tmp_path / "cur", _bench_record(wall=0.08))
        assert not any(f.regressed
                       for f in trend.compare_dirs(cur, base))

    def test_cross_hardware_slowdown_demoted_to_warn(self, tmp_path):
        base = _write_dir(tmp_path / "base",
                          _bench_record(wall=1.0, backend="tpu",
                                        device="TPU v4"))
        cur = _write_dir(tmp_path / "cur", _bench_record(wall=10.0))
        findings = trend.compare_dirs(cur, base)
        walls = [f for f in findings if f.field == "wall_clock_s"]
        assert walls and all(f.level == "warn" for f in walls)
        assert not any(f.regressed for f in findings)

    def test_missing_bench_is_a_regression(self, tmp_path):
        base = _write_dir(tmp_path / "base", _bench_record("serve"),
                          _bench_record("kernels"))
        cur = _write_dir(tmp_path / "cur", _bench_record("serve"))
        findings = trend.compare_dirs(cur, base)
        gone = [f for f in findings if f.regressed]
        assert len(gone) == 1 and gone[0].bench == "kernels" \
            and gone[0].field == "presence"

    def test_new_bench_without_baseline_warns_only(self, tmp_path):
        base = _write_dir(tmp_path / "base", _bench_record("serve"))
        cur = _write_dir(tmp_path / "cur", _bench_record("serve"),
                         _bench_record("fresh"))
        findings = trend.compare_dirs(cur, base)
        assert not any(f.regressed for f in findings)
        assert any(f.bench == "fresh" and f.level == "warn"
                   for f in findings)

    def test_metric_percentiles_gated_like_wall_clock(self, tmp_path):
        m_base = {"serve.request.latency_s.p99": 0.01,
                  "serve.requests.count": 64}
        m_cur = {"serve.request.latency_s.p99": 5.0,
                 "serve.requests.count": 64}
        base = _write_dir(tmp_path / "base",
                          _bench_record(metrics=m_base))
        cur = _write_dir(tmp_path / "cur", _bench_record(metrics=m_cur))
        findings = trend.compare_dirs(cur, base)
        bad = {f.field for f in findings if f.regressed}
        assert bad == {"metrics.serve.request.latency_s.p99"}

    def test_empty_rows_fails(self, tmp_path):
        base = _write_dir(tmp_path / "base", _bench_record(rows=3))
        cur = _write_dir(tmp_path / "cur", _bench_record(rows=0))
        findings = trend.compare_dirs(cur, base)
        assert any(f.regressed and f.field == "rows" for f in findings)

    def test_no_baselines_raises(self, tmp_path):
        cur = _write_dir(tmp_path / "cur", _bench_record())
        os.makedirs(tmp_path / "base")
        with pytest.raises(FileNotFoundError):
            trend.compare_dirs(cur, tmp_path / "base")

    def test_unknown_schema_rejected(self, tmp_path):
        rec = _bench_record()
        rec["schema_version"] = 99
        d = _write_dir(tmp_path / "v", rec)
        with pytest.raises(ValueError):
            trend.load_dir(d)

    def test_format_report_orders_failures_first(self, tmp_path):
        base = _write_dir(tmp_path / "base", _bench_record(wall=1.0))
        cur = _write_dir(tmp_path / "cur", _bench_record(wall=10.0))
        report = trend.format_report(trend.compare_dirs(cur, base))
        assert "1 regression(s)" in report.splitlines()[0]
        assert "[FAIL]" in report.splitlines()[1]
