"""Algorithm 1 on a four-device mesh through the front door,
``ODMEstimator(..., mesh=Mesh(devices, ("data",))).fit``, against a plain
reference and the one-device fit of the same data and key.

At p = 2, three levels and four devices, levels K = 8 and 4 run sharded
(two partitions, then one, per device) and K = 2 and 1 replicated. One
subprocess with four virtual CPU devices runs every fit (the main pytest
process keeps its single device) and prints what the tests read.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_SCRIPT = r"""
import dataclasses, json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.analysis.invariants import counter
from repro.api import ODMEstimator, ProblemSpec
from repro.core import partition as part_mod, sodm
from repro.observe import SpanRecorder, install

k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
M, d = 256, 5
x = jnp.concatenate([jax.random.normal(k0, (M // 2, d)) + 0.3,
                     jax.random.normal(k1, (M // 2, d)) - 0.3])
y = jnp.concatenate([jnp.ones(M // 2), -jnp.ones(M // 2)])
x_te = jax.random.normal(k2, (64, d))
problem = ProblemSpec.create("rbf", gamma=0.5, lam=100.0, theta=0.1,
                             ups=0.5)
cfg = sodm.SODMConfig(engine="pallas", p=2, levels=3, n_landmarks=4,
                      tol=1e-4, max_sweeps=300)
mesh = Mesh(np.array(jax.devices()), ("data",))
key = jax.random.PRNGKey(3)
replicated = counter("sodm.replicated_passes")


def fit(on):
    rec, c0 = SpanRecorder(), replicated.count
    with install(rec):
        model, rep = ODMEstimator(problem, cfg=cfg, mesh=on).fit(x, y, key)
    return {"passes": list(rep.passes),
            "levels": [e["args"] for e in rec.spans("cascade.level")],
            "replicated_passes": replicated.count - c0,
            "alpha": np.asarray(rep.raw.alpha).tolist(),
            "perm": np.asarray(rep.raw.perm).tolist(),
            "f": np.asarray(model.decision_function(x_te)).tolist()}, rep


out = {"x": np.asarray(x).tolist(), "y": np.asarray(y).tolist(),
       "x_te": np.asarray(x_te).tolist()}
out["single"], _ = fit(None)
out["mesh"], rep = fit(mesh)
a = rep.raw.alpha
out["copies"] = {"replicated": a.sharding.is_fully_replicated,
                 "devices": len(a.sharding.device_set),
                 "bitwise": all(np.array_equal(np.asarray(s.data),
                                               np.asarray(a.addressable_shards[0].data))
                                for s in a.addressable_shards)}

# partitioning: what _solve_sharded hands the level loop, per strategy
sodm._level_loop = lambda run_level, x, y, perm, cfg, **kw: perm
spec = problem.kernel
want = {"cluster": part_mod.cluster_partitions(spec, x, 8, key),
        "identity": jnp.arange(M),
        "random": part_mod.random_partitions(M, 8, key)}
out["partition"] = {}
for strategy, perm in want.items():
    c = dataclasses.replace(cfg, partition_strategy=strategy)
    got = sodm._solve_sharded(spec, x, y, problem.params, c, key, mesh)
    out["partition"][strategy] = bool(np.array_equal(np.asarray(got),
                                                     np.asarray(perm)))
bad = dataclasses.replace(cfg, partition_strategy="nope")
for name, call in (("mesh", lambda: sodm._solve_sharded(
        spec, x, y, problem.params, bad, key, mesh)),
                   ("single", lambda: sodm._solve(
        spec, x, y, problem.params, bad, key))):
    try:
        call()
        out["partition"]["raises_" + name] = False
    except ValueError:
        out["partition"]["raises_" + name] = True
print("RESULT " + json.dumps(out))
"""

GAMMA, LAM, THETA, UPS, TOL = 0.5, 100.0, 0.1, 0.5, 1e-4


@pytest.fixture(scope="module")
def fits():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and line, proc.stderr[-4000:]
    return json.loads(line[-1][len("RESULT "):])


def _rbf(a, b):
    d2 = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
    return np.exp(-GAMMA * d2)


def test_mesh_fit_meets_the_plain_reference(fits):
    """Full-problem KKT residual of the mesh fit's duals at most ``tol``
    (float64, dense), and the artifact's decision function equal to the
    dense expansion of the same duals."""
    fit = fits["mesh"]
    perm = np.asarray(fit["perm"])
    x = np.asarray(fits["x"], np.float64)[perm]
    y = np.asarray(fits["y"], np.float64)[perm]
    a = np.asarray(fit["alpha"], np.float64)
    M = x.shape[0]
    zeta, beta = a[:M], a[M:]
    u = y * (_rbf(x, x) @ (y * (zeta - beta)))
    c = (1.0 - THETA) ** 2 / (LAM * UPS)
    g = np.concatenate([u + M * c * UPS * zeta + (THETA - 1.0),
                        -u + M * c * beta + (THETA + 1.0)])
    kkt = np.max(np.where(a > 0.0, np.abs(g), np.maximum(-g, 0.0)))
    assert kkt <= TOL
    f_ref = _rbf(np.asarray(fits["x_te"], np.float64), x) @ (
        y * (zeta - beta))
    f = np.asarray(fit["f"])
    assert np.max(np.abs(f - f_ref)) <= 1e-5 * max(1.0, np.max(np.abs(f_ref)))


def test_mesh_fit_matches_the_one_device_fit_pass_for_pass(fits):
    single, mesh = fits["single"], fits["mesh"]
    assert mesh["passes"] == single["passes"]
    assert len(mesh["passes"]) == 4
    assert mesh["perm"] == single["perm"]
    # both duals meet the KKT tolerance of one problem whose dual is
    # strongly convex with modulus at least M c ups (the diagonal
    # regularizer), so they differ on the scale of tol / (M c ups)
    M = len(mesh["perm"])
    mu = M * (1.0 - THETA) ** 2 / (LAM * UPS) * UPS
    np.testing.assert_allclose(mesh["alpha"], single["alpha"], rtol=0,
                               atol=2 * TOL / mu)


def test_level_spans_carry_layout_devices_and_passes(fits):
    mesh = fits["mesh"]["levels"]
    assert [lv["K"] for lv in mesh] == [8, 4, 2, 1]
    assert [lv["layout"] for lv in mesh] == ["sharded", "sharded",
                                             "replicated", "replicated"]
    for lv, passes in zip(mesh, fits["mesh"]["passes"]):
        assert lv["n_dev"] == 4 and len(lv["passes_by_device"]) == 4
        assert max(lv["passes_by_device"]) == lv["passes"] == passes
        if lv["layout"] == "replicated":
            assert lv["passes_by_device"] == [passes] * 4
    for lv, passes in zip(fits["single"]["levels"],
                          fits["single"]["passes"]):
        assert lv["layout"] == "single" and lv["n_dev"] == 1
        assert lv["passes_by_device"] == [passes]


def test_level_spans_carry_the_kernels_step_counts(fits):
    """At these sizes a partition is one tile, so each lockstep group
    holds one real tile: its slots are the group size times its steps. A
    tile applies at least one step in a pass, and at most 2m."""
    import jax.numpy as jnp
    from repro.kernels.dual_cd_block import group_size
    G = group_size(jnp.float32)
    for run in ("single", "mesh"):
        for lv in fits[run]["levels"]:
            steps, passes = lv["tile_steps"], max(lv["passes_by_device"])
            assert lv["step_slots"] == G * steps
            assert (steps > 0) == (passes > 0)
            assert steps <= passes * lv["K"] * 2 * lv["m"]


def test_replicated_passes_count_the_tails_repeats(fits):
    mesh = fits["mesh"]
    tail = sum(lv["passes"] for lv in mesh["levels"]
               if lv["layout"] == "replicated")
    assert tail > 0
    assert mesh["replicated_passes"] == 3 * tail
    assert fits["single"]["replicated_passes"] == 0


def test_replicated_output_copies_are_bitwise_equal(fits):
    assert fits["copies"] == {"replicated": True, "devices": 4,
                              "bitwise": True}


@pytest.mark.parametrize("strategy", ["cluster", "identity", "random"])
def test_mesh_partitions_as_asked(fits, strategy):
    assert fits["partition"][strategy]


@pytest.mark.parametrize("layout", ["mesh", "single"])
def test_unknown_partition_strategy_raises(fits, layout):
    assert fits["partition"]["raises_" + layout]
