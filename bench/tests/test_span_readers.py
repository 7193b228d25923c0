"""The readers of the program's slab-loop counters and compile spans, on
small hand-made span lists: the value they compute, and no reading where
the spans are absent (a program that records none of them)."""
import pytest

from harness import cells, runner


def _readings(spans):
    return runner.Readings(None, spans, {}, None, 0.0, None)


def _read(metric, spans):
    return cells.load_module("metrics", metric).read(_readings(spans))


def _ev(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": 1, "args": args}


# two fits over 0..4 s (us); the window is their span, 4 s
FITS = [_ev("fit", 0.0, 1.5e6, id=1, parent=None),
        _ev("fit", 2e6, 2e6, id=2, parent=None)]
PASSES = [_ev("dsvrg.pass", 0.1e6, 1e6, id=3, parent=1, kind="anchor",
              epoch=0, wait_s=0.2, h2d_s=0.3, dispatch_s=0.4),
          _ev("dsvrg.pass", 2.5e6, 1e6, id=4, parent=2, kind="final",
              epoch=1, wait_s=0.1, h2d_s=0.5, dispatch_s=0.2)]


@pytest.mark.parametrize("metric,secs", [
    ("slab_wait_pct.stream", 0.3),
    ("h2d_pct.stream", 0.8),
    ("dispatch_pct.stream", 0.6),
])
def test_slab_loop_share_of_the_fits_window(metric, secs):
    assert _read(metric, FITS + PASSES) == pytest.approx(100 * secs / 4)
    assert _read(metric, FITS) is None
    assert _read(metric, PASSES) is None


def test_compile_share_is_the_union_inside_the_fits():
    compiles = [
        _ev("compile.trace", 0.2e6, 0.4e6, id=5, parent=1),
        _ev("compile.backend", 0.5e6, 0.3e6, id=6, parent=1),  # overlaps
        _ev("compile.cache_load", 0.6e6, 0.1e6, id=7, parent=1),
        _ev("compile.lower", 1.4e6, 0.4e6, id=8, parent=None),  # half in
        _ev("compile.trace", 5e6, 1e6, id=9, parent=None),      # outside
    ]
    got = _read("compile_pct.fit", FITS + compiles)
    assert got == pytest.approx(100 * (0.6 + 0.1) / 4)


def test_compile_share_absent_or_zero():
    # a program without the compile listener records spans without ids
    bare = [dict(f, args={}) for f in FITS]
    assert _read("compile_pct.fit", bare) is None
    assert _read("compile_pct.fit", []) is None
    # one with it, that compiled nothing inside its fits, reads 0
    assert _read("compile_pct.fit", FITS) == 0.0
