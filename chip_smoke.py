#!/usr/bin/env python3
"""Run SODM's train-and-serve path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the SPMD cascade on four chips only

One chip (the default) drives the user entry points at the published
shapes of the paper's data sets, with data made from ``--seed``:

* Algorithm 1: ``ODMEstimator`` with the Pallas level engine, rbf kernel,
  on ijcnn1's shape (141,691 x 22, split 80/20). Every level's partitions
  are above ``gram_threshold``, so every pass is the matrix-free fused CD
  kernel. Checked: the final KKT residual is at most ``tol``; held-out
  accuracy is in the band the paper-faithful scalar engine reaches (its
  materialized Gram only fits a row subsample, fit alongside the Pallas
  engine on the same subsample); the served decision function matches
  the dense oracle ``odm.decision_function``.
* Algorithm 2: the linear DSVRG route, streamed from a generator source at
  SUSY's shape (5,000,000 x 18) with the ``examples/sodm_large.py``
  configuration. Checked: held-out accuracy against the generator's Bayes
  rate.
* Serving: the fitted rbf artifact through ``MicrobatchScorer`` for request
  batches of 1 to 256 rows. Checked: scores match ``score_ref``. The
  linear artifact of Algorithm 2 goes through the same batches; its scores
  must match float64 products on the host.

Every phase also checks that its compiled program holds the Pallas kernel
(a ``tpu_custom_call``), not the jnp reference behind ``fused=None`` /
``tiled=None``, and the fit checks that no materialized-Gram fallback fired.

``--chips 4`` runs only what exists across chips: the ijcnn1 rbf fit on a
4-device ``data`` mesh against the one-chip fit with the same data and key,
and sharded scoring against one-device scoring.

Each phase prints its wall time with compilation reported apart. A failed
check exits non-zero. The last line of a passing run is one JSON object
naming the device. Without a TPU the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# published sizes (Table 1 of the paper); the generators make the data
IJCNN1 = "ijcnn1"
SUSY_ROWS = 5_000_000
SUSY_FEATURES = 18
REF_FRACTION = 16         # scalar reference: 1/16 of the training rows
ORACLE_ROWS = 1024        # held-out rows scored against the dense oracle
SERVE_BATCHES = (1, 2, 5, 64, 100, 256)
ACC_BAND = 0.01           # accuracy band half-width
SCORE_RTOL = 1e-4         # served scores vs dense oracles, relative
KERNEL_MARK = "tpu_custom_call"


class Smoke:
    """Phase runner: times each phase (XLA compilation apart) and records
    every failed check; a failure anywhere makes the run exit non-zero.

    The compile clock sums only XLA's backend compilations, which never
    nest; tracing and lowering (whose events do nest) stay in "rest"."""

    _COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.failures: list[str] = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self._COMPILE_EVENT:
            self.compile_s += duration

    def check(self, name: str, ok: bool, detail: str) -> bool:
        print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failures.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        out = None
        try:
            out = fn(self, *args)
        except Exception:                      # recorded, never swallowed
            traceback.print_exc()
            self.failures.append(f"{name} raised")
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"== {name}: wall {wall:.3f} s (backend compile {comp:.3f} s,"
              f" rest {wall - comp:.3f} s)", flush=True)
        return out


def _bootstrap():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it "
                 f"from a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)


def _require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: "
                 f"{dev.platform} {dev.device_kind}); nothing was run")
    from repro.kernels import ops
    if ops._INTERPRET is not False:
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")
    return dev


def _holds_kernel(lowered) -> int:
    """tpu_custom_call sites in a lowered program."""
    return lowered.as_text().count(KERNEL_MARK)


# ---------------------------------------------------------------------------
# Algorithm 1: partitioned dual CD, Pallas engine, rbf
# ---------------------------------------------------------------------------

def _ijcnn1(seed: int):
    import jax
    from repro.core import kernel_fns as kf
    from repro.data import synthetic
    # one program instead of op-by-op dispatch (each op compiles alone)
    arrays = jax.jit(lambda: tuple(synthetic.load(IJCNN1, seed=seed))[:4])()
    ds = synthetic.Dataset(*arrays, name=IJCNN1)
    spec = synthetic.PAPER_DATASETS[IJCNN1]
    n = ds.x_train.shape[0] + ds.x_test.shape[0]
    print(f"{IJCNN1}: {n} rows x {ds.x_train.shape[1]} (published "
          f"{spec.n} x {spec.d}; the generator keeps a multiple of 8), "
          f"train {ds.x_train.shape[0]}, test {ds.x_test.shape[0]}",
          flush=True)
    gamma = kf.median_gamma(ds.x_train)
    return ds, gamma


def _problem(gamma: float):
    from repro.api import ProblemSpec
    return ProblemSpec.create("rbf", gamma=gamma, lam=100.0, theta=0.1,
                              ups=0.5)


def _cfg(engine: str):
    from repro.core.sodm import SODMConfig
    return SODMConfig(engine=engine, p=2, levels=3, n_landmarks=8, tol=1e-4,
                      max_sweeps=200)


def _fit(problem, cfg, x, y, seed: int, mesh=None):
    """ODMEstimator.fit, failing loudly on any materialized-Gram fallback."""
    import jax
    from repro.api import ODMEstimator
    from repro.core import engines
    est = ODMEstimator(problem, cfg=cfg, mesh=mesh)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _, report = est.fit(x, y, jax.random.PRNGKey(seed))
    fallback = [str(w.message) for w in caught
                if "no matrix-free Gram lowering" in str(w.message)]
    fallback += sorted(engines._MATERIALIZED_WARNED)
    return est, report, fallback


def phase_reference(s: Smoke, ds, gamma, seed):
    """The scalar engine (paper-faithful, materialized Q) and the Pallas
    engine on the same row subsample: the accuracy band."""
    n = ds.x_train.shape[0] // REF_FRACTION
    n -= n % 8
    print(f"reference subsample: first {n} training rows (1/{REF_FRACTION}:"
          f" the scalar engine materializes each partition's Gram)",
          flush=True)
    x, y = ds.x_train[:n], ds.y_train[:n]
    out = {}
    for engine in ("scalar", "pallas"):
        t0 = time.perf_counter()
        est, rep, _ = _fit(_problem(gamma), _cfg(engine), x, y, seed)
        out[engine] = (est, est.score(ds.x_test, ds.y_test))
        print(f"  {engine}: acc {out[engine][1]:.6f} kkt {rep.kkt:.3e} "
              f"passes/level {list(rep.passes)} "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    acc_s, acc_p = out["scalar"][1], out["pallas"][1]
    s.check("reference: pallas within the scalar band on the subsample",
            abs(acc_p - acc_s) <= ACC_BAND,
            f"pallas {acc_p:.6f} vs scalar {acc_s:.6f} (band +-{ACC_BAND})")
    agree = float((out["scalar"][0].predict(ds.x_test)
                   == out["pallas"][0].predict(ds.x_test)).mean())
    s.check("reference: engines' predictions agree", agree >= 0.99,
            f"{agree:.6f} of {ds.x_test.shape[0]} held-out rows")
    return acc_s


def phase_alg1(s: Smoke, ds, gamma, seed, acc_scalar):
    import jax
    import jax.numpy as jnp
    from repro.core import engines, odm
    cfg = _cfg("pallas")
    problem = _problem(gamma)
    x, y = ds.x_train, ds.y_train
    est, rep, fallback = _fit(problem, cfg, x, y, seed)
    print(f"  fit: passes/level {list(rep.passes)} kkt {rep.kkt:.6e} "
          f"n_sv {rep.n_sv} wall {rep.wall_clock:.3f} s", flush=True)
    s.check("alg1: no materialized-Gram fallback", not fallback,
            str(fallback) if fallback else "none fired")
    s.check("alg1: KKT residual <= tol", rep.kkt <= cfg.tol,
            f"{rep.kkt:.6e} vs tol {cfg.tol}")
    acc = est.score(ds.x_test, ds.y_test)
    if acc_scalar is None:
        s.check("alg1: held-out accuracy in the scalar band", False,
                f"{acc:.6f}; no scalar reference")
    else:
        s.check("alg1: held-out accuracy in the scalar band",
                acc >= acc_scalar - ACC_BAND,
                f"{acc:.6f} vs scalar {acc_scalar:.6f} - {ACC_BAND}")

    # the decision function against the dense oracle on held-out rows
    res = rep.raw
    xq = ds.x_test[:ORACLE_ROWS]
    f_ref = odm.decision_function(problem.kernel, x[res.perm], y[res.perm],
                                  res.alpha, xq)
    f = est.decision_function(xq)
    err = float(jnp.max(jnp.abs(f - f_ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(f_ref))))
    s.check("alg1: decision_function matches the dense oracle",
            err <= SCORE_RTOL * scale,
            f"max err {err:.3e} (scale {scale:.3e}, {xq.shape[0]} rows)")

    # the level solve the fit ran, lowered at the top level's shapes
    m, d = x.shape
    solver = jax.jit(engines.make_local_solver(
        cfg.engine, block=cfg.block, gram_threshold=cfg.gram_threshold,
        adaptive=cfg.adaptive),
        static_argnames=("spec", "params", "tol", "max_sweeps"))
    f32 = jnp.float32
    low = solver.lower(jax.ShapeDtypeStruct((1, m, d), f32),
                       jax.ShapeDtypeStruct((1, m), f32),
                       jax.ShapeDtypeStruct((1, 2 * m), f32),
                       spec=problem.kernel, params=problem.params,
                       tol=cfg.tol, max_sweeps=cfg.max_sweeps)
    n_k = _holds_kernel(low)
    s.check("alg1: level solve holds the Pallas kernels", n_k > 0,
            f"{n_k} {KERNEL_MARK} sites")
    return est


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def phase_serve(s: Smoke, est, ds):
    import jax.numpy as jnp
    from repro.kernels import score
    from repro.serve import MicrobatchScorer
    model = est.model_
    spec = model.spec
    scorer = MicrobatchScorer(model, max_batch=max(SERVE_BATCHES))
    worst = 0.0
    off = 0
    for b in SERVE_BATCHES:
        xb = ds.x_test[off:off + b]
        off += b
        got = scorer.score(xb)
        ref = score.score_ref(xb, model.x_sv, model.coef, kind=spec.name,
                              gamma=spec.gamma, degree=spec.degree,
                              coef0=spec.coef0)
        err = float(jnp.max(jnp.abs(got - ref)))
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        worst = max(worst, err / scale)
        print(f"  batch {b}: max err {err:.3e} (scale {scale:.3e})",
              flush=True)
    s.check("serve: scores match score_ref", worst <= SCORE_RTOL,
            f"worst relative err {worst:.3e} over batches {SERVE_BATCHES}, "
            f"{model.n_sv} SVs")
    s.check("serve: jit cache bounded by the bucket ladder",
            scorer.compiles <= len(scorer.buckets),
            f"{scorer.compiles} traces, {len(scorer.buckets)} buckets")
    import jax
    xb = jax.ShapeDtypeStruct((scorer.max_batch, ds.x_test.shape[1]),
                              jnp.float32)
    n_k = _holds_kernel(scorer._score.lower(xb, *scorer._margs))
    s.check("serve: scorer holds the Pallas kernel", n_k > 0,
            f"{n_k} {KERNEL_MARK} sites")


# ---------------------------------------------------------------------------
# Algorithm 2: streamed DSVRG, linear
# ---------------------------------------------------------------------------

def phase_alg2(s: Smoke, seed):
    import jax
    import jax.numpy as jnp
    from repro.api import ODMEstimator, ProblemSpec
    from repro.core import dsvrg, kernel_fns as kf, odm, sodm
    from repro.data import streaming
    rows = SUSY_ROWS - SUSY_ROWS % 256
    shard_rows, sep = 65_536, 1.5
    print(f"SUSY: {rows} rows x {SUSY_FEATURES} streamed (published "
          f"{SUSY_ROWS}; the streaming driver keeps a multiple of 256)",
          flush=True)
    src = streaming.SyntheticSource(rows, SUSY_FEATURES,
                                    shard_rows=shard_rows, seed=seed,
                                    sep=sep)
    params = odm.ODMParams(lam=100.0, theta=0.1, ups=0.5)
    dcfg = dsvrg.DSVRGConfig(epochs=3, batch=512, schedule="serial",
                             stream_slab=8_192)
    est = ODMEstimator(ProblemSpec(kernel=kf.KernelSpec(name="linear"),
                                   params=params),
                       route="dsvrg",
                       cfg=sodm.SODMConfig(engine="dsvrg", dsvrg=dcfg))
    _, rep = est.fit(src, key=jax.random.PRNGKey(seed))
    print(f"  fit: {dcfg.epochs} epochs, kkt {rep.kkt:.6e} eta "
          f"{rep.eta:.6e} wall {rep.wall_clock:.3f} s "
          f"({dcfg.epochs * rows / rep.wall_clock:.0f} rows/s, compilation "
          f"included)", flush=True)
    # held-out rows: the next shard of the same generator
    probe = streaming.SyntheticSource(rows + shard_rows, SUSY_FEATURES,
                                      shard_rows=shard_rows, seed=seed,
                                      sep=sep)
    xt, yt = probe.read_shard(len(probe.shard_sizes()) - 1)
    acc = est.score(jnp.asarray(xt), jnp.asarray(yt))
    # the classes sit +-sep noise units from the boundary along one axis
    bayes = 0.5 * (1.0 + math.erf(sep / math.sqrt(2.0)))
    s.check("alg2: held-out accuracy near the Bayes rate",
            acc >= bayes - ACC_BAND,
            f"{acc:.6f} on {xt.shape[0]} rows vs Bayes {bayes:.6f} - "
            f"{ACC_BAND}")
    b = min(dcfg.batch, rows)
    C = -(-max(dcfg.stream_slab, b) // b)
    _, inner = dsvrg._make_stream_steps(params, b, dsvrg._resolve_fused(dcfg))
    f32 = jnp.float32
    vec = jax.ShapeDtypeStruct((SUSY_FEATURES,), f32)
    low = inner.lower(vec, vec, vec, jax.ShapeDtypeStruct((), f32),
                      jax.ShapeDtypeStruct((C, b, SUSY_FEATURES), f32),
                      jax.ShapeDtypeStruct((C, b), f32),
                      jax.ShapeDtypeStruct((C, b), f32))
    n_k = _holds_kernel(low)
    s.check("alg2: DSVRG epoch step holds the Pallas kernel", n_k > 0,
            f"{n_k} {KERNEL_MARK} sites")
    return est, xt


def phase_serve_linear(s: Smoke, est, xt):
    """The linear artifact (explicit w) through MicrobatchScorer against
    float64 products on the host."""
    import numpy as np
    from repro.serve import MicrobatchScorer
    w = np.asarray(est.model_.w, np.float64)
    scorer = MicrobatchScorer(est.model_, max_batch=max(SERVE_BATCHES))
    worst = 0.0
    off = 0
    for b in SERVE_BATCHES:
        xb = xt[off:off + b]
        off += b
        got = np.asarray(scorer.score(xb), np.float64)
        ref = np.asarray(xb, np.float64) @ w
        err = float(np.max(np.abs(got - ref)))
        scale = max(1.0, float(np.max(np.abs(ref))))
        worst = max(worst, err / scale)
        print(f"  batch {b}: max err {err:.3e} (scale {scale:.3e})",
              flush=True)
    s.check("serve linear: scores match float64 x @ w", worst <= SCORE_RTOL,
            f"worst relative err {worst:.3e} over batches {SERVE_BATCHES}")


# ---------------------------------------------------------------------------
# four chips: the SPMD cascade and sharded scoring
# ---------------------------------------------------------------------------

def phase_spmd(s: Smoke, ds, gamma, seed):
    import jax.numpy as jnp
    from repro import sharding
    from repro.serve import score_sharded
    n_dev = 4
    mesh = sharding.make_mesh((n_dev,), ("data",))
    cfg = _cfg("pallas")
    problem = _problem(gamma)
    x, y = ds.x_train, ds.y_train
    one, r1, _ = _fit(problem, cfg, x, y, seed)
    print(f"  one chip: passes/level {list(r1.passes)} kkt {r1.kkt:.6e} "
          f"wall {r1.wall_clock:.3f} s", flush=True)
    four, r4, fallback = _fit(problem, cfg, x, y, seed, mesh=mesh)
    print(f"  {n_dev} chips: passes/level {list(r4.passes)} kkt "
          f"{r4.kkt:.6e} wall {r4.wall_clock:.3f} s", flush=True)
    s.check("spmd: no materialized-Gram fallback", not fallback,
            str(fallback) if fallback else "none fired")
    s.check("spmd: KKT residual <= tol", r4.kkt <= cfg.tol,
            f"{r4.kkt:.6e} vs tol {cfg.tol}")
    p1, p4 = one.predict(ds.x_test), four.predict(ds.x_test)
    agree = float(jnp.mean(p1 == p4))
    acc1 = float(jnp.mean(p1 == ds.y_test))
    acc4 = float(jnp.mean(p4 == ds.y_test))
    s.check("spmd: predictions agree with the one-chip fit", agree >= 0.999,
            f"{agree:.6f} of {ds.x_test.shape[0]} rows (acc {acc4:.6f} vs "
            f"{acc1:.6f})")
    sh = r4.raw.alpha.sharding
    s.check("spmd: replicated tail ran on every device",
            sh.is_fully_replicated and sh.device_set == set(mesh.devices.flat),
            str(sh))
    xq = ds.x_test[:ORACLE_ROWS]
    f1 = four.decision_function(xq)
    f4 = score_sharded(four.model_, xq, mesh)
    err = float(jnp.max(jnp.abs(f1 - f4)))
    scale = max(1.0, float(jnp.max(jnp.abs(f1))))
    s.check("spmd: sharded scoring matches one-device scoring",
            err <= SCORE_RTOL * scale,
            f"max err {err:.3e} (scale {scale:.3e}, {xq.shape[0]} rows, "
            f"{four.model_.n_sv} SVs over {n_dev} devices)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD cascade and sharded scoring")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _bootstrap()
    import jax
    dev = _require_tpu()
    n_dev = len(jax.devices())
    print(f"jax {jax.__version__}; device {dev.platform} "
          f"{dev.device_kind} x{n_dev}", flush=True)
    if n_dev < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {n_dev}")

    s = Smoke(jax)
    t0 = time.perf_counter()
    if args.chips == 1:
        fitted = s.phase("alg2 streamed dsvrg", phase_alg2, args.seed)
        if fitted is not None:
            s.phase("serve linear", phase_serve_linear, *fitted)
    data = s.phase("data", lambda _s: _ijcnn1(args.seed))
    if data is not None:
        ds, gamma = data
        if args.chips == 4:
            s.phase("spmd cascade + sharded scoring", phase_spmd, ds, gamma,
                    args.seed)
        else:
            acc_s = s.phase("scalar reference band", phase_reference, ds,
                            gamma, args.seed)
            est = s.phase("alg1 pallas fit", phase_alg1, ds, gamma,
                          args.seed, acc_s)
            if est is not None:
                s.phase("serve", phase_serve, est, ds)

    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}; total "
          f"{time.perf_counter() - t0:.3f} s (backend compile "
          f"{s.compile_s:.3f} s)", flush=True)
    if s.failures:
        print(f"chip_smoke: FAILED {s.failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
