"""Back-to-back fits through ``ODMEstimator.fit`` on a mesh of the cell's
chips (Algorithm 1's parallel partition phase).

Everything of the ``fit`` driver (the draw, the fixed gamma, the warm-up,
the window, ``kkt`` and ``f_gap``), with the estimator given a one-axis
mesh built from the cell's devices as the configuration's ``mesh`` says.
The program runs a level sharded while its partitions fill the axis, and
replicated on every device after that (``repro.core.sodm``).

The check adds ``replica_gap``: the largest ``|a_i - a_0|`` between the
devices' copies of each fit's dual, the replicated tail's output. One
SPMD program run on identical inputs makes them equal bit for bit; a dual
that is not replicated over the whole mesh reads infinity.
"""
from __future__ import annotations

import numpy as np

from drivers import fit
from harness.checks import Check, limits


def replica_gap(alpha, devices) -> float:
    """Largest difference between the devices' copies of ``alpha``."""
    if not alpha.sharding.is_fully_replicated or \
            alpha.sharding.device_set != set(devices):
        return float("inf")
    copies = [np.asarray(s.data) for s in alpha.addressable_shards]
    return max(float(np.max(np.abs(c - copies[0]))) for c in copies)


class Driver(fit.Driver):
    def prepare(self):
        from jax.sharding import Mesh
        from repro.api import ODMEstimator
        super().prepare()
        (self.axis, n), = self.config["mesh"].items()
        self.mesh = Mesh(np.array(self.devices[:n]), (self.axis,))
        self.est = ODMEstimator(self.est.problem, cfg=self.est.cfg,
                                mesh=self.mesh, data_axis=self.axis)

    def counters(self) -> dict:
        from repro.core import sodm
        out = super().counters()
        n = self.mesh.shape[self.axis]
        out["fit.n_dev"] = n
        layout = getattr(sodm, "mesh_layout", None)
        if layout is not None:
            p, L = out["fit.p"], out["fit.levels"]
            out["fit.layouts"] = [layout(p ** (L - i), n)
                                  for i in range(L + 1)]
        return out

    def answers(self):
        devices = list(self.mesh.devices.flat)
        self.replica_gap = max(
            (replica_gap(r.raw.alpha, devices) for *_, r in self.fits),
            default=float("inf"))
        return super().answers()

    def check(self, answers) -> list:
        lim = limits(self.cell.name)
        return super().check(answers) + [
            Check("replica_gap", self.replica_gap, lim["replica_gap"])]
