"""Cells cut to a size a CPU test run can hold (interpret-mode Pallas)."""
from __future__ import annotations

import dataclasses
import json
import time

from harness import cells, runner

# per cell: changes to the data, the traffic and the solver. The tiny fit
# solves to a tenth of the configuration's tol, so that its full-problem
# KKT residual lies under the cell's limit (set at the cell's size) as the
# cell's own does.
TINY = {
    "cod-rna-rbf.fit": ({"rows": 1000}, {}, {"tol": 1e-5}),
    "susy-linear.fit": ({"rows": 20000}, {"shard_rows": 3000}, {}),
}
SEED = 2


def cell(name: str):
    c = cells.load(name)
    data, traffic, solver = TINY[name]
    config = json.loads(json.dumps(c.config))
    config["data"].update(data)
    config["solver"].update(solver)
    return dataclasses.replace(c, config=config,
                               traffic={**c.traffic, **traffic})


def run(name: str, *, traced: bool = False, seconds: float = 1.0,
        tmp_path=None, seed: int = SEED) -> dict:
    """One run of the tiny cell; shards and traces go under ``tmp_path``
    (a fresh directory by default), so runs in parallel share nothing."""
    import tempfile
    from pathlib import Path

    import jax
    c = cell(name)
    driver = cells.load_module("drivers", c.driver).Driver
    with tempfile.TemporaryDirectory() as tmp:
        base = Path(tmp) if tmp_path is None else tmp_path
        driver.cache = base / "cache"
        return runner.run(c, seed, seconds, traced,
                          t_start=time.perf_counter(),
                          devices=jax.devices()[:c.chips],
                          trace_dir=base / "trace", driver_cls=driver)
