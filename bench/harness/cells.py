"""Cell discovery: everything a cell needs is found by name.

``BENCHMARK.json`` lists configurations, cells (``workloads``) and
metrics. A cell's configuration is the JSON file its entry names; its
traffic mix is ``bench/traffic/<traffic>.json``, whose ``driver`` key
names the general generator in ``bench/drivers/<driver>.py``; a
per-layer metric is read by ``bench/metrics/<metric>.py``. Adding a cell,
a traffic mix, a configuration or a metric is adding files and entries:
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic mix file's contents
    end_to_end: tuple     # BENCHMARK.json metric entries this cell reports
    per_layer: tuple

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return cell in listed
    return "moves" not in metric or metric["moves"] in e2e_names


def load(workload: str, spec_path: Path = SPEC) -> Cell:
    """The cell named ``workload`` with its configuration and traffic."""
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}; "
                       f"one of {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in spec["end_to_end"]
                if _reports(m, workload, set()))
    names = {m["name"] for m in e2e}
    layer = tuple(m for m in spec["per_layer"]
                  if _reports(m, workload, names))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
