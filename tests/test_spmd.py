"""SPMD integration: sharded == single-device numerics, elastic resharding.

These run in a subprocess with --xla_force_host_platform_device_count=8
(the main pytest process must keep the single real device for the smoke
tests). One subprocess executes the whole battery to amortize startup.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs, sharding
from repro.core import dsvrg, kernel_fns as kf, odm, sodm
from repro.data import lm as lmdata
from repro.distributed import elastic
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.train import steps as steps_mod

failures = []
def check(name, cond, info=""):
    print(("PASS " if cond else "FAIL ") + name, info)
    if not cond: failures.append(name)

mesh = make_host_mesh((2, 4), ("data", "model"))

# --- 1. sharded train step == unsharded --------------------------------
cfg = configs.get_smoke("granite-8b")
p, axes = M.init_params(jax.random.PRNGKey(0), cfg)
state = steps_mod.TrainState.create(p, use_ef=False)
tc = steps_mod.TrainConfig()
step = steps_mod.make_train_step(cfg, tc)
dc = lmdata.LMDataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
batch = lmdata.batch_at(dc, 0)

s1, m1 = jax.jit(step)(state, batch)

state_axes = steps_mod.TrainState.axes(axes, use_ef=False)
state_sh = sharding.tree_shardings(state_axes, state, mesh)
state_dev = jax.tree.map(lambda x, s: jax.device_put(x, s), state, state_sh)
def wrapped(st, b):
    with sharding.use_mesh(mesh):
        return step(st, b)
s2, m2 = jax.jit(wrapped, in_shardings=(state_sh, None),
                 out_shardings=(state_sh, None))(state_dev, batch)
dl = abs(float(m1["loss"]) - float(m2["loss"]))
check("train_step loss match", dl < 2e-2, f"diff={dl:.2e}")
pd = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
    a.astype(jnp.float32) - b.astype(jnp.float32)))),
    s1["params"], s2["params"])
mx = max(jax.tree.leaves(pd))
check("train_step params match", mx < 5e-2, f"max={mx:.2e}")

# --- 2. MoE arch sharded loss matches ----------------------------------
cfg2 = configs.get_smoke("dbrx-132b")
p2, axes2 = M.init_params(jax.random.PRNGKey(1), cfg2)
b2 = lmdata.batch_at(lmdata.LMDataConfig(vocab=cfg2.vocab, seq_len=16,
                                         global_batch=4), 0)
l_ref, _ = M.loss_fn(p2, b2, cfg2)
with sharding.use_mesh(mesh):
    l_sh, _ = jax.jit(lambda p, b: M.loss_fn(p, b, cfg2))(p2, b2)
d2 = abs(float(l_ref) - float(l_sh))
check("moe sharded loss", d2 < 5e-2, f"diff={d2:.2e}")

# --- 3. SODM solve_sharded == solve ------------------------------------
key = jax.random.PRNGKey(2)
Mn = 128
x = jnp.concatenate([jax.random.normal(key, (Mn//2, 5)) + 1.0,
                     jax.random.normal(jax.random.fold_in(key, 1), (Mn//2, 5)) - 1.0])
y = jnp.concatenate([jnp.ones(Mn//2), -jnp.ones(Mn//2)])
spec = kf.KernelSpec(name="rbf", gamma=0.5)
params = odm.ODMParams()
scfg = sodm.SODMConfig(p=2, levels=3, n_landmarks=4, tol=1e-6, max_sweeps=300)
r1 = sodm.solve(spec, x, y, params, scfg, jax.random.PRNGKey(3))
r2 = sodm.solve_sharded(spec, x, y, params, scfg, jax.random.PRNGKey(3),
                        mesh, data_axis="data")
xp, yp = x[r2.perm], y[r2.perm]
Q = kf.signed_gram(spec, xp, yp)
o2 = float(odm.dual_objective(Q, r2.alpha, params, float(Mn)))
xq, yq = x[r1.perm], y[r1.perm]
o1 = float(odm.dual_objective(kf.signed_gram(spec, xq, yq), r1.alpha,
                              params, float(Mn)))
check("sodm sharded objective", abs(o1 - o2) < 1e-3, f"{o1:.5f} vs {o2:.5f}")
# the replicated tail (K < data axis) runs on every device of the mesh,
# not on whichever device the partitioner would pick
sh = r2.alpha.sharding
check("sodm replicated tail spans the mesh",
      sh.is_fully_replicated and sh.device_set == set(mesh.devices.flat),
      str(sh))

# --- 4. DSVRG solve_sharded --------------------------------------------
# batch 3 ∤ m = 16: the ragged tail is exercised through the SPMD driver;
# eta <= 0 exercises the on-device auto_eta psum on a real multi-device
# mesh (must equal the single-process step size)
for sched in ("parallel", "serial"):
    dcfg = dsvrg.DSVRGConfig(n_partitions=8, epochs=4, batch=3,
                             schedule=sched)
    rr1 = dsvrg.solve(x, y, params, dcfg, jax.random.PRNGKey(4))
    rr2 = dsvrg.solve_sharded(x, y, params, dcfg, jax.random.PRNGKey(4),
                              mesh)
    dd = abs(float(rr1.history[-1]) - float(rr2.history[-1]))
    dw = float(jnp.max(jnp.abs(rr1.w - rr2.w)))
    de = abs(float(rr1.eta) - float(rr2.eta))
    check(f"dsvrg sharded objective ({sched})", dd < 1e-3, f"diff={dd:.2e}")
    check(f"dsvrg sharded w parity ({sched})", dw < 1e-4, f"diff={dw:.2e}")
    check(f"dsvrg sharded auto-eta ({sched})", de < 1e-6, f"diff={de:.2e}")

# --- 4b. SODM dsvrg engine route on the mesh ---------------------------
ecfg = sodm.SODMConfig(engine="dsvrg",
                       dsvrg=dsvrg.DSVRGConfig(n_partitions=8, epochs=6,
                                               batch=4))
spec_lin = kf.KernelSpec(name="linear")
er1 = sodm.solve(spec_lin, x, y, params, ecfg, jax.random.PRNGKey(5))
er2 = sodm.solve_sharded(spec_lin, x, y, params, ecfg, jax.random.PRNGKey(5),
                         mesh, data_axis="data")
a1 = odm.accuracy(y, sodm.predict(spec_lin, er1, x, y, x))
a2 = odm.accuracy(y, sodm.predict(spec_lin, er2, x, y, x))
da = abs(float(a1) - float(a2))
check("sodm dsvrg engine sharded acc", da < 0.005, f"{float(a1):.4f} vs {float(a2):.4f}")

# --- 4d. unified API: estimator fit on the mesh -------------------------
from repro.api import ODMEstimator, ProblemSpec
est = ODMEstimator(ProblemSpec(kernel=spec, params=params), route="sodm",
                   cfg=scfg, mesh=mesh, data_axis="data")
am, ar = est.fit(x, y, jax.random.PRNGKey(3))
ra = ar.raw
oa = float(odm.dual_objective(kf.signed_gram(spec, x[ra.perm], y[ra.perm]),
                              ra.alpha, params, float(Mn)))
check("api estimator sharded sodm objective", abs(oa - o1) < 1e-3,
      f"{oa:.5f} vs {o1:.5f}")
# the artifact leaves the mesh: serving places it (a Pallas scorer cannot
# be partitioned over the devices the fit's duals were replicated on)
check("api estimator sharded artifact is unplaced",
      not am.coef.committed and len(am.coef.sharding.device_set) == 1,
      str(am.coef.sharding))
est_l = ODMEstimator(ProblemSpec(kernel=spec_lin, params=params), cfg=ecfg,
                     mesh=mesh, data_axis="data")
lm_, lr = est_l.fit(x, y, jax.random.PRNGKey(5))
al = odm.accuracy(y, lm_.predict(x))
check("api estimator sharded dsvrg route",
      lr.route == "dsvrg" and abs(float(al) - float(a1)) < 0.005,
      f"route={lr.route} acc={float(al):.4f} vs {float(a1):.4f}")

# --- 4c. serving: SV slab sharded across the data axis ------------------
from repro import serve
smodel = serve.from_sodm(spec, r1, x, y)
f_rep = smodel.decision_function(x[:48])
f_shd = serve.score_sharded(smodel, x[:48], mesh, data_axis="data")
dsv = float(jnp.max(jnp.abs(f_rep - f_shd)))
check("serve sharded SV-slab scores", dsv < 1e-5, f"diff={dsv:.2e}")

# --- 5. elastic resharding (2,4) -> (4,2) ------------------------------
mesh_b = make_host_mesh((4, 2), ("data", "model"))
p_a = elastic.reshard(p, axes, mesh)
p_b = elastic.reshard(p_a, axes, mesh_b)
check("elastic values preserved", elastic.validate_resharding(p, p_b))

# --- 6. checkpoint save on mesh A, restore on mesh B --------------------
import tempfile
from repro.distributed.checkpoint import CheckpointManager
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(1, p_a)
    shard_b = sharding.tree_shardings(axes, p, mesh_b)
    p_c = mgr.restore(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p), shardings=shard_b)
    check("ckpt cross-mesh restore", elastic.validate_resharding(p, p_c))

print("FAILURES:", failures)
raise SystemExit(1 if failures else 0)
"""


@pytest.mark.slow
def test_spmd_battery():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    print(proc.stdout)
    print(proc.stderr[-3000:] if proc.stderr else "")
    assert proc.returncode == 0, "SPMD battery failed (see output)"
