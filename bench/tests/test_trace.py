"""The reduction from a device trace and host spans to metrics."""
import pytest

from harness import layers, trace as tr


def small_trace():
    # two devices; window [5, 35] ns
    return tr.DeviceTrace(devices={
        "/device:TPU:0": [tr.Op("k1", 0, 10), tr.Op("k2", 5, 15),
                          tr.Op("fusion.3", 20, 30, ("k1_long_name",))],
        "/device:TPU:1": [tr.Op("k3", 10, 40)],
    }, t0=5, t1=35)


def test_busy_is_the_union_clipped_to_the_window():
    t = small_trace()
    assert t.busy_intervals("/device:TPU:0") == [[5, 15], [20, 30]]
    assert t.busy_intervals("/device:TPU:1") == [[10, 35]]
    assert t.busy_s() == pytest.approx((20 + 25) / 2 / 1e9)
    assert t.window_s == pytest.approx(30e-9)
    assert t.idle_share() == pytest.approx(0.25)


def test_kernel_time_by_name_or_attribute():
    t = small_trace()
    n, s = t.kernel(r"^k1")
    assert n == 2 and s == pytest.approx(15e-9)      # 5 clipped + 10 by label
    assert t.kernel("nothing") == (0, 0.0)


def test_gaps_named_by_the_innermost_host_span():
    t = small_trace()
    assert sorted(t.gaps()) == [(5, 10), (15, 20), (30, 35)]
    host_t0 = 100.0                     # host seconds at the window's start
    to_us = lambda ns: (host_t0 + (ns - t.t0) / 1e9) * 1e6   # noqa: E731
    spans = [{"name": "fit", "ts": to_us(0), "dur": 40e-3},
             {"name": "data.shard", "ts": to_us(14), "dur": 8e-3}]
    got = dict(tr.name_gaps(t, spans, host_t0))
    assert got["data.shard"] == pytest.approx(5e-9 / 2)
    assert got["fit"] == pytest.approx(10e-9 / 2)


def test_gap_outside_every_span():
    t = small_trace()
    got = dict(tr.name_gaps(t, [], 0.0))
    assert got == {"(no program span)": pytest.approx(15e-9 / 2)}


def test_span_union():
    spans = [{"ts": 0, "dur": 10}, {"ts": 5, "dur": 10}, {"ts": 30, "dur": 5}]
    assert layers.union_s(spans) == pytest.approx(20e-6)
    assert layers.union_s(spans, 8, 32) == pytest.approx(9e-6)


def test_roofline_reader_by_hand():
    from harness import cells, runner
    trace = tr.DeviceTrace(devices={"/device:TPU:0": [
        tr.Op("%odm_svrg_grad.1 = f32[18]", 0, 1000),
        tr.Op("%copy.1 = f32[8192,18]", 1000, 5000),
        tr.Op("%odm_svrg_grad.1 = f32[18]", 5000, 6000)]}, t0=0, t1=10_000)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = runner.Readings(None, [], {"stream.fits": 1, "stream.batch": 512,
                                   "stream.features": 18}, trace, 0.0, peak)
    got = cells.load_module("metrics", "odm_svrg_grad_roofline").read(r)
    # both calls memory-bound: (512 * 18 + 2 * 512 + 4 * 18) * 4 bytes
    least = 2 * (512 * 18 + 1024 + 72) * 4 / 819e9
    assert got == pytest.approx(100 * least / 2e-6)
    assert "0.0 % of it compute-bound" in r.notes[-1]
    r.counters["stream.fits"] = 0                   # no fits: no reading
    assert cells.load_module("metrics", "odm_svrg_grad_roofline").read(
        r) is None
