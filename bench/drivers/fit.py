"""Back-to-back fits through ``ODMEstimator.fit`` (Algorithm 1).

Set-up draws the configuration's data in one jitted program (see
:func:`draw` for what the seed changes), fixes gamma by the median
heuristic on it, and warms up with one fit of the same shapes on rows
spread so far apart that the rbf Gram matrix is the identity: every level
solve converges in a few passes, yet the fit runs, so compiles or loads
from the cache, every program a real fit runs. Only the artifact's
support-vector gathers, whose length is the fit's own count, are left;
where the warm-up had to compile anything (a cold cache), set-up also
runs one real fit, which puts those in the cache too. The window then
fits the same data with the same key again and again; a fit started
inside it runs to completion. ``fit_s`` is the time from the first fit's
start to the last fit's end over the fits completed.

Each fit's answer is judged by the plain reference: the full problem's
KKT residual of its duals (at most the configuration's ``tol``), and the
fitted artifact's decision function on every held-out row against the
dense reference expansion of the same duals.
"""
from __future__ import annotations

import gc
import hashlib
import time
import traceback

import numpy as np

from data import blobs, gamma as gamma_mod, seeds
from harness.checks import Check, limits
from reference import odm as ref


def draw(config: dict, seed: int):
    """The configuration's training and held-out rows for ``seed``.

    Every seed fits the same problem: one draw from the configuration's
    ``data_seed``, whose labels the seed may flip. ``Q = Y K Y`` and the
    partitioning (a function of the rows alone) are unchanged bit for
    bit, so every seed asks the solver for the same work and reads the
    same numbers; the decision function changes sign. Draws from
    different seeds differ by 12.7 % in fit time (PERF.md), far more than
    two runs of one draw, so the draw is fixed.
    """
    import jax
    d = config["data"]
    x, y, x_te, y_te = blobs.make(
        seeds.key(d["data_seed"]), rows=d["rows"], features=d["features"],
        balance=d["balance"], sep=d["sep"],
        train_fraction=d["train_fraction"])
    sign = 1.0 - 2.0 * jax.random.bernoulli(seeds.key(seed, 3))
    return x, sign * y, x_te, sign * y_te


def fixed_gamma(config: dict) -> float:
    """The median heuristic on the configuration's own draw: the same for
    every seed, so every seed runs the same compiled programs."""
    d = config["data"]
    x = blobs.make(seeds.key(d["data_seed"]), rows=d["rows"],
                   features=d["features"], balance=d["balance"],
                   sep=d["sep"], train_fraction=d["train_fraction"])[0]
    return gamma_mod.median_gamma(x)


class Driver:
    def __init__(self, cell, seed: int, devices, log):
        self.cell, self.seed, self.devices, self.log = cell, seed, devices, log
        self.config = cell.config
        self.fits: list = []        # (t0, t1, model, report)
        self.errors = 0

    #: the warm-up's scale of the rows: every off-diagonal rbf entry
    #: underflows to 0 in float32, so the level solves decouple
    SPREAD = 1e4

    def setup(self):
        from harness import runner
        self.prepare()
        compiles = runner.compile_count()
        c0, _ = compiles.snapshot()
        self.est.fit(self.x * self.SPREAD, self.y, self.key)
        if compiles.snapshot()[0] > c0:     # a cold cache: fill it whole
            self._fit()
        self.fits.clear()

    def prepare(self):
        """Data, gamma and the estimator, without the warm-up."""
        from repro.api import ODMEstimator, ProblemSpec
        from repro.core.sodm import SODMConfig
        self.x, self.y, self.x_te, self.y_te = draw(self.config, self.seed)
        self.gamma = fixed_gamma(self.config)
        odm = self.config["odm"]
        problem = ProblemSpec.create(self.config["kernel"]["name"],
                                     gamma=self.gamma, lam=odm["lam"],
                                     theta=odm["theta"], ups=odm["ups"])
        self.est = ODMEstimator(problem, cfg=SODMConfig(
            **self.config["solver"]))
        self.key = seeds.key(self.config["data"]["data_seed"], 1)

    def _fit(self):
        t0 = time.perf_counter()
        model, report = self.est.fit(self.x, self.y, self.key)
        self.fits.append((t0, time.perf_counter(), model, report))

    def window(self, seconds: float):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            try:
                self._fit()
            except Exception:
                traceback.print_exc()
                self.errors += 1
                break

    def attempted(self) -> int:
        return len(self.fits) + self.errors

    def failed(self) -> int:
        return self.errors

    def end_to_end(self) -> dict:
        if not self.fits:
            return {"fit_s": float("inf")}
        span = self.fits[-1][1] - self.fits[0][0]
        return {"fit_s": span / len(self.fits)}

    def counters(self) -> dict:
        M, d = self.x.shape
        s = self.config["solver"]
        return {"fit.passes": [list(r.passes) for *_, r in self.fits],
                "fit.rows": M, "fit.features": d, "fit.p": s["p"],
                "fit.levels": s["levels"], "fit.block": s.get("block", 256)}

    def notes(self) -> list:
        return [f"fit {i}: {t1 - t0:.3f} s passes/level {list(r.passes)} "
                f"kkt {r.kkt!r} n_sv {r.n_sv}"
                for i, (t0, t1, _, r) in enumerate(self.fits)]

    def answers(self):
        """Each distinct dual the window produced, with its artifact's
        decision function on the held-out rows (the served path)."""
        out: dict = {}
        for *_, model, report in self.fits:
            alpha = np.asarray(report.raw.alpha)
            h = hashlib.sha1(alpha.tobytes()).hexdigest()
            if h not in out:
                out[h] = {"alpha": alpha,
                          "perm": np.asarray(report.raw.perm),
                          "f": np.asarray(model.decision_function(
                              self.x_te), np.float64)}
        return list(out.values())

    def free(self):
        self.est = None
        self.fits = [(t0, t1, None, None) for t0, t1, *_ in self.fits]
        gc.collect()

    def check(self, answers) -> list:
        lim = limits(self.cell.name)
        odm = self.config["odm"]
        x = np.asarray(self.x)
        y = np.asarray(self.y)
        kkt, gap = 0.0, 0.0
        for a in answers:
            xp, yp = x[a["perm"]], y[a["perm"]]
            kkt = max(kkt, ref.kkt_residual(xp, yp, a["alpha"], self.gamma,
                                            odm["lam"], odm["theta"],
                                            odm["ups"]))
            f_ref = ref.decision(xp, yp, a["alpha"], self.x_te, self.gamma)
            scale = max(1.0, float(np.max(np.abs(f_ref))))
            gap = max(gap, float(np.max(np.abs(a["f"] - f_ref))) / scale)
        if not answers:
            kkt = gap = float("inf")
        return [Check("kkt", kkt, lim["kkt"]),
                Check("f_gap", gap, lim["f_gap"])]
