"""Serve a trained ODM: fit -> compress -> checkpoint -> microbatch loop.

The full deployment lifecycle of the paper's model on the serving
subsystem (``repro.serve``):

  1. fit SODM and compile the dual into a ``FittedODM`` artifact
     (exact-zero duals pruned into a packed SV slab);
  2. Nyström-compress the slab to a landmark budget within an accuracy
     target (the Eqn. 8 pivoted-Cholesky picks double as Nyström pivots);
  3. save the artifact atomically and reload it (what a serving replica
     would do at startup);
  4. drive a synthetic request stream through the deadline microbatcher
     and report accuracy, latency percentiles and throughput.

    PYTHONPATH=src python examples/serve_odm.py [--scale 0.1] [--budget 64]
"""
import argparse
import time

import jax

from repro import compile_cache
from repro import serve
from repro.api import ODMEstimator, ProblemSpec
from repro.core import kernel_fns as kf, odm, sodm
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--target", type=float, default=0.05,
                    help="max decision-value gap allowed by compression")
    ap.add_argument("--ckpt-dir", default="/tmp/serve_odm_ckpt")
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--rate", type=float, default=5000.0,
                    help="synthetic arrival rate (requests/s)")
    args = ap.parse_args()

    ds = synthetic.load("svmguide1", scale=args.scale, max_d=64)
    M = ds.x_train.shape[0] - ds.x_train.shape[0] % 8
    x, y = ds.x_train[:M], ds.y_train[:M]
    problem = ProblemSpec(
        kernel=kf.KernelSpec(name="rbf", gamma=kf.median_gamma(x)),
        params=odm.ODMParams(lam=100.0, theta=0.1, ups=0.5))
    cfg = sodm.SODMConfig(p=2, levels=3, n_landmarks=8, tol=1e-4,
                          max_sweeps=200)

    # 1. fit + compile through the unified API (the permutation gather
    # and SV packing happen once; training output IS the artifact)
    est = ODMEstimator(problem, route="sodm", cfg=cfg)
    model, report = est.fit(x, y, jax.random.PRNGKey(0))
    print(f"[fit] M={M} -> {model.n_sv} SVs ({model.compression}) "
          f"in {report.wall_clock:.1f}s  [{report.summary()}]")

    # 2. compress to the landmark budget within the accuracy target
    comp = serve.compress(model, args.budget, target=args.target)
    print(f"[compress] {model.n_sv} -> {comp.n_sv} SVs "
          f"({comp.compression}, decision gap {comp.gap:.4f})")

    # 3. checkpoint round trip (what a replica does at startup)
    comp.save(args.ckpt_dir)
    served = serve.load_model(args.ckpt_dir)
    print(f"[ckpt] saved + reloaded from {args.ckpt_dir} "
          f"({served.compression}, {served.n_sv} SVs)")

    for name, m in (("exact", model), ("served", served)):
        acc = float(odm.accuracy(ds.y_test, m.predict(ds.x_test)))
        print(f"[accuracy] {name}: {acc:.4f}")

    # 4. microbatched serving loop over a synthetic arrival stream
    scorer = serve.MicrobatchScorer(served, max_batch=128)
    batcher = serve.Batcher(scorer, max_batch=64, max_wait=2e-3)
    T = ds.x_test.shape[0]
    arrivals = ((i / args.rate, ds.x_test[i % T])
                for i in range(args.requests))
    t0 = time.time()
    stats = serve.serve_stream(batcher, arrivals)
    wall = time.time() - t0
    print(f"[serve] {len(stats['results'])} requests in {wall:.2f}s wall "
          f"({len(stats['results']) / max(wall, 1e-9):.0f} rps), "
          f"mean batch {stats['mean_batch']:.1f}, "
          f"latency p50 {stats['p50'] * 1e3:.2f}ms "
          f"p95 {stats['p95'] * 1e3:.2f}ms, "
          f"jit cache {scorer.compiles}/{len(scorer.buckets)} buckets")


if __name__ == "__main__":
    compile_cache.enable()
    main()
