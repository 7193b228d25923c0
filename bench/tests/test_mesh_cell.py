"""The four-chip fit cell rehearsed on four virtual CPU devices (interpret
mode, a tiny draw), and its readers on hand-made spans, traces and
levels. A rehearsal of the control flow, never a measurement.

The runs go in one subprocess: the virtual devices must be set before
JAX starts, and this process keeps the one CPU device.
"""
import json
import os
import subprocess
import sys

import pytest

from harness import cells, runner, trace as trace_mod

CELL = "cod-rna-rbf.fit-mesh4"
BENCH = cells.BENCH

_SCRIPT = r"""
import dataclasses, json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "..", "src")]
import jax, jax.numpy as jnp
from harness import cells, runner

c = cells.load(sys.argv[2])
config = json.loads(json.dumps(c.config))
# a small draw: the CPU profiler holds every interpret-mode kernel step
config["data"]["rows"] = 200
config["solver"]["tol"] = 1e-5          # as the tiny one-chip fit cell
c = dataclasses.replace(c, config=config)
base = cells.load_module("drivers", c.driver).Driver


class Perturbed(base):
    # one device's copy of the replicated tail's dual moved by 1e-3
    def _fit(self):
        super()._fit()
        t0, t1, model, rep = self.fits[-1]
        a = rep.raw.alpha
        shards = [s.data + (1e-3 if i == 1 else 0.0)
                  for i, s in enumerate(a.addressable_shards)]
        bad = jax.make_array_from_single_device_arrays(a.shape, a.sharding,
                                                       shards)
        rep = dataclasses.replace(rep, raw=rep.raw._replace(alpha=bad))
        self.fits[-1] = (t0, t1, model, rep)


out = {}
for name, traced, drv in (("untraced", False, base), ("traced", True, base),
                          ("perturbed", False, Perturbed)):
    out[name] = runner.run(c, 2, 1.0, traced, t_start=time.perf_counter(),
                           devices=jax.devices()[:4],
                           trace_dir=os.path.join(sys.argv[3], name),
                           driver_cls=drv)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(BENCH), CELL,
         str(tmp_path_factory.mktemp("mesh"))],
        env=env, capture_output=True, text=True, timeout=900)
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and line, proc.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def test_untraced_run_is_correct(runs):
    out = runs["untraced"]
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"kkt", "f_gap", "replica_gap"}
    assert out["checks"]["replica_gap"]["value"] == 0.0
    assert set(out["metrics"]) == {m["name"]
                                   for m in cells.load(CELL).end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["count"] == 4


def test_traced_run_reads_its_layers(runs):
    out = runs["traced"]
    assert out["correct"], out["checks"]
    layer = {m["name"] for m in cells.load(CELL).per_layer}
    # the CPU trace holds no TPU device, so the device-trace readers
    # stay silent
    on_cpu = {"cd_passes.fit", "outside_levels_pct.fit", "compile_pct.fit",
              "replicated_tail_pct.mesh4", "mesh_straggle_pct.mesh4"}
    assert set(out["metrics"]) == layer & on_cpu
    tail = out["metrics"]["replicated_tail_pct.mesh4"]["value"]
    assert 0.0 < tail < 100.0
    assert 0.0 <= out["metrics"]["mesh_straggle_pct.mesh4"]["value"] < 100.0


def test_a_perturbed_replica_trips_replica_gap(runs):
    out = runs["perturbed"]
    assert not out["correct"]
    gap = out["checks"]["replica_gap"]
    assert gap["value"] == pytest.approx(1e-3, rel=1e-3)
    assert gap["value"] > gap["limit"]
    assert out["checks"]["kkt"]["value"] <= out["checks"]["kkt"]["limit"]


def _read(metric, spans=(), counters=None, trace=None):
    r = runner.Readings(None, list(spans), counters or {}, trace, 0.0, None)
    return cells.load_module("metrics", metric).read(r)


def _ev(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": 1, "args": args}


def _level(ts, dur, layout, by_dev, K=8, m=100):
    return _ev("cascade.level", ts, dur, level=0, K=K, m=m, layout=layout,
               n_dev=len(by_dev), passes_by_device=by_dev,
               passes=max(by_dev))


# two fits, 4 s and 5 s (us): 2.5 s and 2 s of replicated levels
SPANS = [_ev("fit", 0.0, 4e6), _ev("fit", 5e6, 5e6),
         _level(0.5e6, 1e6, "sharded", [4, 2, 4, 2]),
         _level(1.5e6, 1e6, "replicated", [6, 6, 6, 6]),
         _level(2.5e6, 1.5e6, "replicated", [3, 3, 3, 3]),
         _level(5.5e6, 1e6, "sharded", [8, 8, 8, 8]),
         _level(7e6, 2e6, "replicated", [5, 5, 5, 5])]


def test_replicated_tail_share_of_the_fits():
    assert _read("replicated_tail_pct.mesh4", SPANS) == \
        pytest.approx(100 * 4.5 / 9)
    # a program whose level spans carry no layout gives no reading
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("layout", "passes_by_device")})
            for e in SPANS]
    assert _read("replicated_tail_pct.mesh4", bare) is None


def test_straggle_share_of_the_sharded_levels():
    # level 1: max 4, mean 3; level 2: max 8, mean 8
    assert _read("mesh_straggle_pct.mesh4", SPANS) == \
        pytest.approx(100 * 1 / 12)
    only_tail = [e for e in SPANS
                 if e["args"].get("layout") != "sharded"]
    assert _read("mesh_straggle_pct.mesh4", only_tail) is None


def _trace(ops_by_device, t0=0, t1=10_000):
    return trace_mod.DeviceTrace(
        devices={f"/device:TPU:{i}": [trace_mod.Op(n, a, b) for n, a, b in ops]
                 for i, ops in enumerate(ops_by_device)}, t0=t0, t1=t1)


def test_collective_share_counts_what_nothing_else_covers():
    gather = "%all-gather.8 = f32[4,1,23808]{2,1,0} all-gather(%p)"
    start = "%async-collective-start = (f32[1,1,23808]) fusion(%b)"
    done = "%async-collective-done = f32[4,1,23808] fusion(%g)"
    tr = _trace([
        # 1,000 ns alone, 500 of a 1,000-ns one under a fusion
        [(gather, 0, 1000), (start, 2000, 3000), ("%fusion.32 = (f32[1])",
                                                  2500, 4000)],
        # a consumer that names the collective as its operand is compute
        [(done, 5000, 5500), ("%reduce.5 = f32[4] reduce(%async-collective"
                              "-done)", 6000, 9000)],
    ])
    r = runner.Readings(None, [], {}, tr, 0.0, None)
    got = cells.load_module("metrics", "collective_pct.mesh4").read(r)
    assert got == pytest.approx(100 * (1500 + 500) / 2 / 10_000)
    assert _read("collective_pct.mesh4") is None


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_roofline_work_counts_each_level_once(n_dev):
    cost = cells.load_module("cost", "fused_cd_pass")
    roof = cells.load_module("metrics", "fused_cd_pass_roofline.mesh4")
    K, m, d, B, passes = 8, 5952, 8, 256, 7
    for layout in ("sharded", "replicated"):
        parts = roof.work(cost, [(K, m, layout, [passes] * n_dev)], d, B)
        flops = sum(f * n for f, _, n in parts)
        nbytes = sum(b * n for _, b, n in parts)
        want = cost.cost(K, m, d, B)
        assert flops == pytest.approx(passes * want[0])
        assert nbytes == pytest.approx(passes * want[1])
        assert sum(n for *_, n in parts) == passes * n_dev


def test_roofline_needs_one_call_a_pass_on_every_device():
    roof = cells.load_module("metrics", "fused_cd_pass_roofline.mesh4")
    levels = roof.levels_of(SPANS)
    assert [lv[2] for lv in levels] == ["sharded", "replicated",
                                       "replicated", "sharded",
                                       "replicated"]
    assert _read("fused_cd_pass_roofline.mesh4", SPANS) is None  # no trace
    assert _read("fused_cd_pass_roofline.mesh4", []) is None
