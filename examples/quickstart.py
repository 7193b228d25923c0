"""Quickstart: train SODM through the unified API and evaluate.

    PYTHONPATH=src python examples/quickstart.py

One front door for every training route: describe the problem with a
``ProblemSpec``, hand it to ``ODMEstimator``, get back a deployable
``FittedODM`` artifact plus a uniform ``FitReport`` — whichever solver
the registry resolves (Alg. 1 partitioned dual CD here; Alg. 2 DSVRG for
the linear kernel below).
"""
import jax

from repro import compile_cache
from repro.api import ODMEstimator, ProblemSpec
from repro.core import dsvrg, kernel_fns as kf, sodm
from repro.data import synthetic


def main():
    # a stand-in for the paper's `phishing` set (11k x 68, scaled to CPU)
    ds = synthetic.load("phishing", scale=0.05)
    M = ds.x_train.shape[0] - ds.x_train.shape[0] % 8
    x, y = ds.x_train[:M], ds.y_train[:M]
    print(f"dataset: {ds.name}  train={x.shape}  test={ds.x_test.shape}")

    # the 10-line front door: spec -> estimator -> artifact + report
    problem = ProblemSpec.create("rbf", gamma=kf.median_gamma(x),
                                 lam=100.0, theta=0.1, ups=0.5)
    est = ODMEstimator(problem, cfg=sodm.SODMConfig(
        p=2, levels=3, n_landmarks=8, tol=1e-4, max_sweeps=200))
    model, report = est.fit(x, y, jax.random.PRNGKey(0))
    print(report.summary())
    print(f"test accuracy: {est.score(ds.x_test, ds.y_test):.4f}")

    # linear-kernel path (DSVRG, Algorithm 2) — same door, another route.
    # Large linear problems reach this route automatically; naming it
    # keeps the demo explicit.
    lin = ODMEstimator(
        ProblemSpec.create("linear", lam=100.0, theta=0.1, ups=0.5),
        route="dsvrg",
        cfg=sodm.SODMConfig(dsvrg=dsvrg.DSVRGConfig(
            n_partitions=8, epochs=8, batch=16)))   # eta <= 0: auto step
    _, rep = lin.fit(x, y, jax.random.PRNGKey(1))
    print(f"DSVRG (linear) test accuracy: "
          f"{lin.score(ds.x_test, ds.y_test):.4f} "
          f"obj history: {[round(h, 4) for h in rep.history]}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
