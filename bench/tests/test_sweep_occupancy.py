"""The reader of the fused CD kernel's lockstep counters, on hand-made
level spans: the share it computes, and no reading where the spans carry
no counts (a program whose kernel sweeps one tile at a time)."""
import pytest

from harness import cells, runner


def _read(spans):
    r = runner.Readings(None, spans, {}, None, 0.0, None)
    return cells.load_module("metrics", "sweep_occupancy.fit").read(r)


def _level(i, **args):
    return {"name": "cascade.level", "ph": "X", "ts": float(i), "dur": 1.0,
            "pid": 1, "tid": 1,
            "args": dict(id=i, parent=None, level=i, passes=3, **args)}


def test_share_of_the_step_slots_over_all_levels():
    spans = [_level(0, tile_steps=300, step_slots=400),
             _level(1, tile_steps=50, step_slots=200, layout="replicated"),
             _level(2)]                      # a level with no counts
    assert _read(spans) == pytest.approx(100.0 * 350 / 600)


@pytest.mark.parametrize("spans", [[], [_level(0), _level(1)],
                                   [_level(0, tile_steps=0, step_slots=0)]])
def test_no_reading_without_counts(spans):
    assert _read(spans) is None
