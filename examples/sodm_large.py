"""End-to-end SODM driver: the paper's training pipeline at scale.

    PYTHONPATH=src python examples/sodm_large.py [--rows 2000000]
    PYTHONPATH=src python examples/sodm_large.py --dense [--engine pallas]
    PYTHONPATH=src python examples/sodm_large.py --handloop [--resume]

This is the 'train a model for real' driver of deliverable (b): a
SUSY-shaped problem (the paper's 5M-row set) sized by ``--rows``.

Default path: train PAST host RAM. The data is a
:class:`repro.data.streaming.SyntheticSource` — a generator whose shard
``i`` is a pure function of ``(seed, i)``, so ``--rows`` can exceed what
the host could ever materialize (the dataset occupies zero disk and is
never resident). ``ODMEstimator.fit(source)`` streams it through the
prefetch loader into the out-of-core DSVRG route; a
:class:`~repro.data.streaming.ByteAccountant` proves the point by
printing peak resident data bytes next to the dataset's logical size.

``--dense`` keeps the previous resident-API demo (route resolution,
per-level checkpointing via the ``level_callback`` hook); ``--handloop``
keeps the hand-rolled production-runtime demo: stratified partitioning,
level-parallel solves dispatched through the speculative straggler
scheduler, per-level checkpointing, and ``--resume`` restart — the
subsystems the estimator hides.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.api import ODMEstimator, ProblemSpec
from repro.core import dual_cd, kernel_fns as kf, odm, partition, sodm
from repro.core.dsvrg import DSVRGConfig
from repro.data import streaming, synthetic
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.straggler import SpecConfig, SpeculativeScheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000,
                    help="streamed training rows — set this past host "
                         "RAM freely; the generator source is never "
                         "materialized")
    ap.add_argument("--features", type=int, default=18)   # SUSY's d
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--shard-rows", type=int, default=65_536)
    ap.add_argument("--dense", action="store_true",
                    help="previous resident-data estimator demo (SUSY "
                         "stand-in + sodm route) instead of streaming")
    ap.add_argument("--resume", action="store_true",
                    help="restart from the latest checkpoint (--handloop)")
    ap.add_argument("--handloop", action="store_true",
                    help="hand-rolled level loop with the speculative "
                         "straggler scheduler instead of the estimator")
    ap.add_argument("--ckpt-dir", default="/tmp/sodm_large_ckpt")
    ap.add_argument("--scale", type=float, default=0.002)   # of 5M rows
    ap.add_argument("--engine", default="scalar",
                    choices=("scalar", "block", "pallas"),
                    help="local solver for --dense/--handloop: "
                         "paper-faithful scalar CD, the jnp block "
                         "oracle, or the Pallas greedy block-CD tile "
                         "kernel")
    args = ap.parse_args()
    if args.handloop and args.engine == "block":
        ap.error("--handloop dispatches per-partition solves (scalar | "
                 "pallas); the block engine is a level solver — drop "
                 "--handloop to use it")

    if not (args.dense or args.handloop):
        return stream(args)

    ds = synthetic.load("SUSY", scale=args.scale)
    M = ds.x_train.shape[0] - ds.x_train.shape[0] % 32
    x, y = ds.x_train[:M], ds.y_train[:M]
    print(f"SUSY stand-in: train={x.shape}")

    spec = kf.KernelSpec(name="rbf", gamma=kf.median_gamma(x))
    params = odm.ODMParams(lam=100.0, theta=0.1, ups=0.5)
    p_factor, levels = 2, 5            # 32 partitions

    if args.handloop:
        return handloop(args, spec, x, y, params, p_factor, levels, ds)

    # --- the resident front door ------------------------------------------
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    est = ODMEstimator(
        ProblemSpec(kernel=spec, params=params),
        route="sodm",
        cfg=sodm.SODMConfig(p=p_factor, levels=levels, n_landmarks=8,
                            tol=1e-4, max_sweeps=150, engine=args.engine))

    def checkpoint_level(level, alphas):
        # same fault-tolerance contract as the hand loop: every finished
        # level is an atomic versioned restart point
        mgr.save(levels - level + 1, alphas,
                 {"level": level, "n_partitions": int(alphas.shape[0])})

    t0 = time.time()
    model, report = est.fit(x, y, jax.random.PRNGKey(0),
                            level_callback=checkpoint_level)
    print(report.summary())
    print(f"trained + compiled {model.n_sv} SVs in {time.time() - t0:.1f}s")
    print(f"final test accuracy: {est.score(ds.x_test, ds.y_test):.4f}")


def stream(args):
    """Train beyond host RAM: generator source -> out-of-core DSVRG."""
    rows = args.rows - args.rows % 256
    src = streaming.SyntheticSource(rows, args.features,
                                    shard_rows=args.shard_rows, seed=0,
                                    sep=1.5)
    print(f"generator source: {rows} rows x {args.features} features = "
          f"{src.total_bytes / 1e9:.2f} GB logical, 0 bytes resident")

    est = ODMEstimator(
        ProblemSpec(kernel=kf.KernelSpec(name="linear"),
                    params=odm.ODMParams(lam=100.0, theta=0.1, ups=0.5)),
        route="dsvrg",
        cfg=sodm.SODMConfig(engine="dsvrg", dsvrg=DSVRGConfig(
            epochs=args.epochs, batch=512, schedule="serial",
            stream_slab=8_192)))
    acct = streaming.ByteAccountant()
    t0 = time.time()
    model, report = est.fit(src, key=jax.random.PRNGKey(0),
                            accountant=acct)
    wall = time.time() - t0
    print(report.summary())
    print(f"streamed {args.epochs} epochs over {rows} rows in {wall:.1f}s "
          f"({args.epochs * rows / wall:.0f} rows/s)")
    print(f"peak resident data bytes: {acct.peak:,} "
          f"({acct.peak / src.total_bytes:.1%} of the dataset)")

    # held-out rows from the SAME generator distribution: shard i is a
    # pure function of (seed, i), so a longer source's first shards are
    # the training stream and its extra shard is fresh test data
    probe = streaming.SyntheticSource(rows + args.shard_rows,
                                      args.features,
                                      shard_rows=args.shard_rows, seed=0,
                                      sep=1.5)
    x_test, y_test = probe.read_shard(len(probe.shard_sizes()) - 1)
    acc = float(odm.accuracy(jnp.asarray(y_test),
                             model.predict(jnp.asarray(x_test))))
    print(f"held-out accuracy on {x_test.shape[0]} fresh rows: {acc:.4f}")


def handloop(args, spec, x, y, params, p_factor, levels, ds):
    """The PR 1-era production runtime, kept as the subsystem demo."""
    M = x.shape[0]
    K = p_factor ** levels
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    sched = SpeculativeScheduler(SpecConfig(max_workers=8))

    # --- partition (Section 3.2) ------------------------------------
    t0 = time.time()
    plan = partition.make_plan(spec, x, n_landmarks=8, n_partitions=K,
                               key=jax.random.PRNGKey(0))
    xp, yp = x[plan.perm], y[plan.perm]
    print(f"stratified partitioning: {K} partitions, "
          f"{time.time() - t0:.1f}s")

    # --- hierarchical solve with checkpoint/restart -------------------
    start_level = levels
    m = M // K
    alphas = jnp.zeros((K, 2 * m))
    if args.resume and mgr.latest_step() is not None:
        meta = mgr.metadata()
        start_level = meta["metadata"]["level"] - 1
        K_res = meta["metadata"]["n_partitions"]
        m = M // K_res
        alphas = mgr.restore(jax.ShapeDtypeStruct((K_res, 2 * m),
                                                  jnp.float32))
        K = K_res
        print(f"resumed at level {start_level} (K={K})")

    level = start_level
    while True:
        xs = xp.reshape(K, m, -1)
        ys = yp.reshape(K, m)
        t0 = time.time()

        # partition solves are pure + idempotent: dispatch through the
        # speculative scheduler (first-completion wins on duplicates)
        def _prep(Q, ak):
            # merged children were solved at scale m/p; the ray rescale
            # conditions them to this level's scale (see
            # repro.core.odm.warm_start_scale / sodm's scale note)
            zk, bk = odm.split_alpha(ak)
            u = Q @ (zk - bk)
            t = odm.warm_start_scale(u, ak, params, float(m))
            return ak * t, u * t

        if args.engine == "pallas":
            from repro.kernels import ops

            def _pallas_one(xk, yk, ak):
                Q = kf.signed_gram(spec, xk, yk)
                ak, _ = _prep(Q, ak)
                alpha, _, _ = ops.dual_cd_solve(
                    Q, c=params.c, ups=params.ups, theta=params.theta,
                    mscale=float(m), n_passes=150, tol=1e-4, alpha0=ak)
                return alpha
            solve_one = jax.jit(_pallas_one)
        else:
            def _scalar_one(xk, yk, ak):
                Q = kf.signed_gram(spec, xk, yk)
                ak, uk = _prep(Q, ak)
                return dual_cd.solve(Q, params, mscale=float(m), alpha0=ak,
                                     u0=uk, tol=1e-4, max_sweeps=150).alpha
            solve_one = jax.jit(_scalar_one)
        tasks = [(lambda i=i: solve_one(xs[i], ys[i], alphas[i]))
                 for i in range(K)]
        results = sched.run(tasks)
        alphas = jnp.stack(results)
        print(f"level {level}: solved {K} partitions of {m} rows "
              f"in {time.time() - t0:.1f}s")
        mgr.save(levels - level + 1, alphas,
                 {"level": level, "n_partitions": K})

        if K == 1:
            break
        Kn = K // p_factor
        grouped = alphas.reshape(Kn, p_factor, 2 * m)
        alphas = jax.vmap(sodm.merge_alphas)(grouped)
        K, m = Kn, m * p_factor
        level -= 1

    alpha = alphas.reshape(-1)
    pred = odm.predict(spec, xp, yp, alpha, ds.x_test)
    print(f"final test accuracy: {float(odm.accuracy(ds.y_test, pred)):.4f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
