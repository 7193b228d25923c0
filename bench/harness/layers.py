"""Shared arithmetic of the per-layer readers in ``bench/metrics/``."""
from __future__ import annotations


def union_s(spans: list, t0_us: float | None = None,
            t1_us: float | None = None) -> float:
    """Seconds covered by the union of span events (``ts``/``dur`` in us),
    optionally clipped to ``[t0_us, t1_us]``."""
    iv = []
    for e in spans:
        a, b = e["ts"], e["ts"] + e["dur"]
        if t0_us is not None:
            a, b = max(a, t0_us), min(b, t1_us)
        if b > a:
            iv.append((a, b))
    iv.sort()
    tot, end = 0.0, None
    for a, b in iv:
        if end is None or a > end:
            tot += b - a
            end = b
        elif b > end:
            tot += b - end
            end = b
    return tot / 1e6


def idle_pct(r) -> float | None:
    """Share of the traced window in which the cell's devices ran nothing,
    averaged over the devices (1 - busy / window), in %."""
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * r.trace.idle_share()


def roofline_pct(r, kernel: str, work) -> float | None:
    """Share of a kernel's roofline: the least time the chip could take
    for the work the kernel did, over the kernel's device time, in %.

    ``work(cost_module, calls)`` lists ``(flops, bytes, n)`` for the calls
    the trace holds (``n`` calls of that size), or returns None where the
    trace's call count does not match the work counted; no calls, no
    reading. The least time of a call is the larger of its operations
    over peak FLOP/s and its bytes over peak bandwidth; the reading's
    bound (the share of the least time that is compute) goes to ``r.notes``.
    """
    from harness import cells, peaks
    if r.trace is None or r.peak is None:
        return None
    mod = cells.load_module("cost", kernel)
    calls, secs = r.trace.kernel(mod.PATTERN)
    if calls == 0 or secs <= 0.0:
        return None
    parts = work(mod, calls)
    if parts is None:
        r.notes.append(f"{kernel}: {calls} calls in the trace do not match "
                       f"the work counted; no reading")
        return None
    least = compute = 0.0
    for flops, nbytes, n in parts:
        t, bound = peaks.least_time(flops, nbytes, r.peak)
        least += n * t
        compute += n * t if bound == "compute" else 0.0
    r.notes.append(f"{kernel}: {calls} calls, {secs!r} s on the device, "
                   f"least {least!r} s ({100 * compute / least:.1f} % of it "
                   f"compute-bound)")
    return 100.0 * least / secs
