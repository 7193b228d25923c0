"""Every cell end to end at a tiny size on the CPU (interpret mode): a
rehearsal of the control flow, never a measurement."""
import pytest

import tiny
from harness import cells

READABLE_ON_CPU = {"cd_passes.fit", "outside_levels_pct.fit",
                   "shard_read_pct.stream"}


@pytest.mark.parametrize("name", list(tiny.TINY))
def test_untraced_run_is_correct(name):
    out = tiny.run(name)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"], out["checks"]
    want = {m["name"] for m in cells.load(name).end_to_end}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("name", list(tiny.TINY))
def test_traced_run_reads_its_layers(name, tmp_path):
    out = tiny.run(name, traced=True, tmp_path=tmp_path)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and "breakdown" in out
    assert {"busy_s", "window_s"} <= set(out["device"])
    layer = {m["name"] for m in cells.load(name).per_layer}
    assert set(out["metrics"]) == layer & READABLE_ON_CPU
