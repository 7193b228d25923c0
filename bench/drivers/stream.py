"""Back-to-back streamed fits through ``ODMEstimator.fit(source)``
(Algorithm 2, the ``dsvrg`` route).

Set-up draws the configuration's rows from the seed in one jitted
program, writes them as ``.npy`` shards under ``bench/.cache/stream/``
(what an earlier run left there is removed first, and the run removes
its own shards when it frees the program), opens them through the
program's memory-mapped ``NpyShardSource``, and runs one whole fit to
compile, or load from the cache, every program the window runs (and to
bring the shards into the page cache, as a deployment that trains
repeatedly has them). The window fits again and again; a fit started
inside it runs to completion. ``stream_rows_per_s`` is the rows of all
completed epochs over the time from the first fit's start to the last
fit's end.

Each fit's weights are judged against the float64 replay of the same
serial chain on the host (``reference.odm.dsvrg_stream``), and the data
plane by its read counts: every shard is read ``2 epochs + 1`` times a
fit (an anchor pass and an inner pass per epoch, and the closing
gradient pass), no more and no fewer.
"""
from __future__ import annotations

import gc
import hashlib
import shutil
import time
import traceback

import numpy as np

from data import blobs, seeds
from harness import cells
from harness.checks import Check, limits
from reference import odm as ref


class Driver:
    #: where the shards are written (the tests point it elsewhere)
    cache = cells.BENCH / ".cache" / "stream"

    def __init__(self, cell, seed: int, devices, log):
        self.cell, self.seed, self.devices, self.log = cell, seed, devices, log
        self.config = cell.config
        self.dsvrg = dict(self.config["solver"]["dsvrg"])
        self.fits: list = []        # (t0, t1, w, report)
        self.errors = 0

    def setup(self):
        self.prepare()
        self._fit()                 # warm-up: compiles or loads everything
        self.fits.clear()
        self.reads0 = list(self.source.reads)

    def prepare(self):
        from repro.api import ODMEstimator, ProblemSpec
        from repro.core.dsvrg import DSVRGConfig
        from repro.core.sodm import SODMConfig
        from repro.data import streaming
        d = self.config["data"]
        x, y, _, _ = blobs.make(seeds.key(self.seed), rows=d["rows"],
                                features=d["features"],
                                balance=d["balance"], sep=d["sep"],
                                train_fraction=d["train_fraction"])
        self.x = np.asarray(x)
        self.y = np.asarray(y)
        del x, y
        self.source = streaming.NpyShardSource(self._write_shards())
        odm = self.config["odm"]
        problem = ProblemSpec.create("linear", lam=odm["lam"],
                                     theta=odm["theta"], ups=odm["ups"])
        self.est = ODMEstimator(problem, route="dsvrg", cfg=SODMConfig(
            engine="dsvrg", dsvrg=DSVRGConfig(**self.dsvrg)))
        self.key = seeds.key(self.seed, 1)

    def _write_shards(self) -> list:
        self.shards = self.cache / self.config["name"]
        shutil.rmtree(self.shards, ignore_errors=True)
        here = self.shards / f"seed-{self.seed}"
        here.mkdir(parents=True)
        rows = int(self.cell.traffic["shard_rows"])
        pairs = []
        for s, lo in enumerate(range(0, self.x.shape[0], rows)):
            xp = here / f"shard_{s:05d}_x.npy"
            yp = here / f"shard_{s:05d}_y.npy"
            np.save(xp, self.x[lo:lo + rows])
            np.save(yp, self.y[lo:lo + rows])
            pairs.append((str(xp), str(yp)))
        return pairs

    def _fit(self):
        t0 = time.perf_counter()
        model, report = self.est.fit(self.source, key=self.key)
        w = np.asarray(model.w)
        self.fits.append((t0, time.perf_counter(), w, report))

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
            return {"stream_rows_per_s": 0.0}
        rows = len(self.fits) * self.dsvrg["epochs"] * self.x.shape[0]
        return {"stream_rows_per_s":
                rows / (self.fits[-1][1] - self.fits[0][0])}

    def counters(self) -> dict:
        return {"stream.fits": len(self.fits),
                "stream.rows": int(self.x.shape[0]),
                "stream.features": int(self.x.shape[1]),
                "stream.epochs": self.dsvrg["epochs"],
                "stream.batch": self.dsvrg["batch"]}

    def notes(self) -> list:
        rows = self.dsvrg["epochs"] * self.x.shape[0]
        return [f"fit {i}: {t1 - t0:.3f} s ({rows / (t1 - t0):.0f} rows/s) "
                f"kkt {r.kkt!r} eta {r.eta!r}"
                for i, (t0, t1, _, r) in enumerate(self.fits)]

    def answers(self):
        reads = [b - a for a, b in zip(self.reads0, self.source.reads)]
        ws: dict = {}
        for *_, w, _ in self.fits:
            ws.setdefault(hashlib.sha1(w.tobytes()).hexdigest(), w)
        return {"w": list(ws.values()), "reads": reads,
                "fits": len(self.fits)}

    def free(self):
        self.est = self.source = None
        self.fits = [(t0, t1, None, None) for t0, t1, *_ in self.fits]
        gc.collect()
        shutil.rmtree(self.shards, ignore_errors=True)

    def check(self, answers) -> list:
        lim = limits(self.cell.name)
        odm = self.config["odm"]
        per_fit = 2 * self.dsvrg["epochs"] + 1
        off = max((abs(r - answers["fits"] * per_fit)
                   for r in answers["reads"]), default=float("inf"))
        gap = float("inf")
        if answers["w"]:
            w_ref, _ = ref.dsvrg_stream(
                lambda lo, hi: (self.x[lo:hi], self.y[lo:hi]),
                self.x.shape[0], self.x.shape[1], lam=odm["lam"],
                theta=odm["theta"], ups=odm["ups"],
                epochs=self.dsvrg["epochs"], batch=self.dsvrg["batch"])
            scale = float(np.max(np.abs(w_ref)))
            gap = max(float(np.max(np.abs(w - w_ref))) / scale
                      for w in answers["w"])
        return [Check("w_gap", gap, lim["w_gap"]),
                Check("shard_reads_off", off, lim["shard_reads_off"])]
