"""Cell discovery from files, and ``BENCHMARK.json`` against its contract."""
import json
import re

import pytest

from harness import cells
from harness.checks import LIMITS

SPEC = json.loads(cells.SPEC.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int) and \
        1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_entries_and_names():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for group, allowed in keys.items():
        for e in SPEC[group]:
            assert set(e) == allowed, e
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert len(WORKLOADS) == len(set(WORKLOADS))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


def test_chips():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves_from_files(workload):
    c = cells.load(workload)
    assert c.chips == c.config["chips"]
    assert hasattr(cells.load_module("drivers", c.driver), "Driver")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(cells.load_module("metrics", m["name"]).read)
    assert (LIMITS / f"{workload}.json").is_file()


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        doc = json.loads((cells.ROOT / c["file"]).read_text())
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])


def test_a_new_cell_needs_only_an_entry(tmp_path):
    """A cell added to BENCHMARK.json with existing files resolves with no
    change to the harness."""
    spec = json.loads(cells.SPEC.read_text())
    spec["workloads"].append({"name": "cod-rna-rbf.fit-again",
                              "config": "cod-rna-rbf", "traffic": "fit",
                              "chips": 1, "why": "a copy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "cod-rna-rbf.fit" in m.get("workloads", []):
            m["workloads"].append("cod-rna-rbf.fit-again")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    c = cells.load("cod-rna-rbf.fit-again", spec_path=path)
    assert c.driver == "fit"
    assert {m["name"] for m in c.per_layer} == \
        {m["name"] for m in cells.load("cod-rna-rbf.fit").per_layer}


def test_unknown_workload():
    with pytest.raises(KeyError):
        cells.load("no-such.cell")
