"""Benchmark-script rot guard (ISSUE 2 satellite).

The paper-table and kernel-micro bench scripts are not exercised by the
unit suite, so API refactors could silently break them. This smoke tier
(a) imports every module registered in ``benchmarks.run`` (catches
syntax/import rot) and (b) *executes* the scripts named in the issues —
``kernels_bench``, ``table2_rbf``, ``table3_linear`` and
``fig4_gradient`` — through their quick paths, so every jit/pallas entry
point they touch actually compiles.
Runs under ``-m "not slow"``; the ``bench_smoke`` marker (pytest.ini) lets
callers deselect it separately.
"""
import importlib
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

pytestmark = pytest.mark.bench_smoke

BENCH_MODULES = ["run", "common", "kernels_bench", "table2_rbf",
                 "table3_linear", "table4_svm", "fig2_speedup",
                 "fig4_gradient", "roofline_report", "serve_bench",
                 "data_bench"]


@pytest.mark.parametrize("name", BENCH_MODULES)
def test_bench_module_imports(name):
    importlib.import_module(f"benchmarks.{name}")


def test_run_registry_covers_all_tables():
    from benchmarks import run
    assert set(run.ALL) == {"table2", "table3", "table4", "fig2", "fig4",
                            "kernels", "roofline", "serve", "data"}


def test_bench_persist_schema(tmp_path):
    """ISSUE 7 satellite (schema bumped to v2 by ISSUE 9): `python -m
    benchmarks.run --quick --out-dir D` persists a BENCH_<name>.json per
    bench with route, wall-clock, peak bytes, device kind, and an
    instrument-snapshot `metrics` dict, so CI runs leave artifacts the
    perf gate can trend."""
    import json
    from benchmarks import run

    rc = run.main(["kernels", "--quick", "--out-dir", str(tmp_path)])
    assert rc == 0
    path = tmp_path / "BENCH_kernels.json"
    assert path.exists()
    rec = json.loads(path.read_text())
    assert rec["schema_version"] == 2
    assert rec["bench"] == "kernels"
    assert rec["backend"] and rec["device_kind"] and rec["jax_version"]
    assert rec["wall_clock_s"] > 0
    assert isinstance(rec["peak_bytes"], int)    # 0 on CPU is fine
    assert rec["rows"] == len(rec["lines"]) > 0
    assert any(line.startswith("kernels,") for line in rec["lines"])
    assert isinstance(rec["metrics"], dict)      # {} for metric-less benches
    # the trend loader accepts what run.py persists
    from repro.observe import trend
    assert trend.load_dir(tmp_path)["kernels"]["bench"] == "kernels"
    # no torn temp file left behind
    assert not list(tmp_path.glob("*.tmp"))


def test_bench_cli_rejects_unknowns(tmp_path, capsys):
    from benchmarks import run
    assert run.main(["nope"]) == 1
    assert "unknown benchmark" in capsys.readouterr().out
    assert run.main(["--out-dir"]) == 1


def test_kernels_bench_quick_executes():
    """Compile-and-run the full kernels_bench script path at toy sizes.

    Also pins the fused-pass acceptance numbers: exactly one pallas_call
    per pass, one matvec launch saved vs the PR 1 layout.
    """
    from benchmarks import kernels_bench
    out = []
    kernels_bench.run(out, quick=True)
    assert any(line.startswith("kernels,sodm_level_pallas") for line in out)
    for name in ("linear", "rbf", "laplacian", "poly"):
        assert any(f"gram_matvec_{name}" in line for line in out), name
    fused = [line for line in out if "fused_pass_op_count" in line]
    assert len(fused) == 1
    assert "pallas_calls_per_pass_fused=1" in fused[0]
    assert "matvec_launches_saved=1" in fused[0]
    # serving scorer pins (ISSUE 4 satellite): one pallas_call per request
    # batch, tile scratch a fraction of the dense (T, S) Gram bytes
    sc = [line for line in out if "serve_score_op_count" in line]
    assert len(sc) == 1
    assert "pallas_calls_per_batch=1" in sc[0]
    dense = int(sc[0].split("dense_gram_bytes=")[1].split("_")[0])
    tile = int(sc[0].split("tile_scratch_bytes=")[1].split(",")[0])
    assert tile < dense, (tile, dense)
    assert any("serve_score_blocked" in line for line in out)


def test_table2_rbf_quick_executes():
    """One tiny data set through the full table-2 harness (all methods)."""
    from benchmarks import table2_rbf
    out = []
    # one data set at ~1/10 scale: the ~15s floor is the jit compiles of
    # the five methods, not the solve — small enough for the fast tier
    table2_rbf.run(out, datasets=["svmguide1"], scale_factor=0.1)
    methods = {line.split(",")[2] for line in out
               if line.startswith("table2,svmguide1")}
    assert {"SODM", "SODM-blk", "Ca-ODM", "DiP-ODM", "DC-ODM"} <= methods
    assert any(line.startswith("table2,summary") for line in out)


def test_table3_linear_quick_executes():
    """The linear benchmark can no longer rot silently (ISSUE 3 satellite).

    Executes the full table-3 harness on one tiny data set and pins the
    acceptance criterion: the DSVRG engine route (`SODMConfig.engine=
    "dsvrg"` through ODMEstimator) lands within 0.5 accuracy points of the
    dual-CD level-loop path on the quick data set.
    """
    from benchmarks import table3_linear
    out = []
    table3_linear.run(out, datasets=["svmguide1"], scale_factor=0.1)
    rows = {line.split(",")[2]: float(line.split(",")[3]) for line in out
            if line.startswith("table3,svmguide1")}
    assert {"SODM(dsvrg)", "SODM(dsvrg-eng)", "SODM(dual-cd)", "Ca-ODM",
            "DiP-ODM", "DC-ODM"} <= set(rows)
    gap = abs(rows["SODM(dsvrg-eng)"] - rows["SODM(dual-cd)"])
    assert gap <= 0.005 + 1e-9, f"engine-vs-dual-CD accuracy gap {gap}"
    assert any(line.startswith("table3,summary") for line in out)


def test_fig2_speedup_quick_executes():
    """The last previously-untested benchmark script (ISSUE 4 satellite):
    the scheduling-model figure runs end to end at quick scale and emits
    both regimes' speedup curves."""
    from benchmarks import fig2_speedup
    out = []
    fig2_speedup.run(out, quick=True)
    for regime in ("tight", "loose"):
        assert any(line.startswith(f"fig2,{regime},32,") for line in out), \
            regime
        assert any(f"fig2,{regime},sweeps_per_level" in line
                   for line in out), regime


def test_serve_bench_quick_executes():
    """Serving acceptance (ISSUE 4): the compressed/microbatched path must
    beat the naive dense predict on wall-clock at quick scale, peak
    scoring memory must be below the dense (T, M) Gram, and the jit cache
    must stay inside the bucket ladder (asserted inside the script too)."""
    from benchmarks import serve_bench
    out = []
    metrics = serve_bench.run(out, quick=True)
    # ISSUE 9: the bench returns an instrument snapshot that lands in
    # BENCH_serve.json's "metrics" field — histogram-derived latency
    # percentiles plus request/batch accounting
    for k in ("serve.request.latency_s.p50", "serve.request.latency_s.p95",
              "serve.request.latency_s.p99", "serve.requests.count",
              "serve.batches.count", "serve.queue_depth.max"):
        assert k in metrics, k
    assert metrics["serve.requests.count"] == 64
    summary = [line for line in out if "compressed_beats_dense" in line][0]
    assert summary.split(",")[3] == "1", summary
    peak = [line for line in out if line.startswith("serve,peak_bytes")][0]
    dense = int(peak.split("dense=")[1].split(",")[0])
    tiled = int(peak.split("tiled=")[1].split("_")[0])
    assert tiled < dense, peak
    art = [line for line in out if line.startswith("serve,artifact")][0]
    n_sv = int(art.split("n_sv=")[1].split(",")[0])
    comp = int(art.split("compressed_sv=")[1].split("_")[0])
    assert comp <= max(8, n_sv // 4), art
    assert any(line.startswith("serve,stream") for line in out)
    assert any(line.startswith("serve,jit_cache") for line in out)


def test_fig4_gradient_quick_executes():
    """One tiny data set through the gradient-methods figure script; all
    three methods must share the device-computed DSVRG step size."""
    from benchmarks import fig4_gradient
    out = []
    fig4_gradient.run(out, datasets=[("a7a", 0.01)])
    methods = {line.split(",")[2] for line in out if line.startswith("fig4,")}
    assert {"DSVRG", "SVRG", "CSVRG", "eta"} <= methods
    eta = float([line for line in out if ",eta," in line][0].split(",")[3])
    assert eta > 0.0
