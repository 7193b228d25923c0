"""The device trace of a ``--trace 1`` run, reduced to what readers need.

The JAX profiler writes an ``.xplane.pb``; :func:`load` turns it into
plain lists, and everything else here works on those lists, so the tests
can feed a small recorded trace. Times are nanoseconds on the profiler's
clock. The harness wraps its measured window in a host annotation named
:data:`WINDOW`; device events are clipped to it.

* busy time of a device: the union of the intervals of the operations on
  its ``XLA Ops`` line (operations may nest; the union counts each
  instant once);
* idle share: 1 - busy / window, averaged over the cell's devices;
* kernel time: the summed durations of the operations whose name, or a
  string attribute, matches the kernel's pattern;
* idle gaps: the intervals of the window in which a device ran nothing,
  named by the innermost host span of the program open at the time.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    end: float            # ns
    labels: tuple = ()    # string attributes (hlo_op, long names, ...)


@dataclasses.dataclass
class DeviceTrace:
    """Per-device operations clipped to the window ``[t0, t1]`` (ns)."""

    devices: dict          # plane name -> list[Op]
    t0: float
    t1: float

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, device: str) -> list:
        """Merged busy intervals of one device inside the window."""
        spans = sorted((max(o.start, self.t0), min(o.end, self.t1))
                       for o in self.devices[device])
        merged: list = []
        for a, b in spans:
            if b <= a:
                continue
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return merged

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(b - a for a, b in self.busy_intervals(d))
                  for d in self.devices)
        return tot / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def gaps(self) -> list:
        """Idle intervals ``(a, b)`` (ns) of every device in the window."""
        out = []
        for d in self.devices:
            t = self.t0
            for a, b in self.busy_intervals(d):
                if a > t:
                    out.append((t, a))
                t = max(t, b)
            if t < self.t1:
                out.append((t, self.t1))
        return out

    def kernel(self, pattern: str) -> tuple[int, float]:
        """(calls, seconds) of the operations matching ``pattern``, summed
        over the devices (clipped to the window)."""
        rx = re.compile(pattern)
        n, tot = 0, 0.0
        for ops in self.devices.values():
            for o in ops:
                if rx.search(o.name) or any(rx.search(s) for s in o.labels):
                    a, b = max(o.start, self.t0), min(o.end, self.t1)
                    if b > a:
                        n += 1
                        tot += b - a
        return n, tot / 1e9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` operations that took most device time, by the first
        100 characters of their HLO text (name and result shape)."""
        by: dict = {}
        for ops in self.devices.values():
            for o in ops:
                a, b = max(o.start, self.t0), min(o.end, self.t1)
                if b > a:
                    n = o.name[:100]
                    by[n] = by.get(n, 0.0) + (b - a) / 1e9
        return sorted(([n, s] for n, s in by.items()),
                      key=lambda p: -p[1])[:k]


def _string_stats(ev) -> tuple:
    out = []
    for item in ev.stats:
        try:
            _, v = item
        except (TypeError, ValueError):
            continue
        if isinstance(v, str):
            out.append(v)
    return tuple(out)


def load(trace_dir: str, *, window: str = WINDOW):
    """The :class:`DeviceTrace` of the newest
    ``.xplane.pb`` under ``trace_dir``; the window is the host annotation
    named ``window``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: dict = {}
    t0 = t1 = None
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  _string_stats(ev)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window:
                        t0, t1 = ev.start_ns, ev.start_ns + ev.duration_ns
    if t0 is None:
        raise ValueError(f"the trace holds no host annotation {window!r}")
    return DeviceTrace(devices=devices, t0=t0, t1=t1)


def name_gaps(trace: DeviceTrace, spans: list, host_t0_s: float,
              k: int = 10) -> list:
    """Idle device time by what the host was doing: each gap is split
    over the innermost program span open in it (``spans`` are
    SpanRecorder events on the host's ``perf_counter`` clock in us;
    ``host_t0_s`` is the host time at which the window annotation
    opened). Returns the ``k`` largest ``[name, seconds]`` totals,
    averaged over the devices."""
    import bisect
    evs = []
    for e in spans:                                  # to the profiler clock
        a = (e["ts"] / 1e6 - host_t0_s) * 1e9 + trace.t0
        evs.append((a, a + e["dur"] * 1e3, e["name"]))
    # elementary segments between span boundaries, each named by the
    # shortest (innermost) span covering it
    cuts = sorted({t for a, b, _ in evs for t in (a, b)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        inner = min(((eb - ea, n) for ea, eb, n in evs if ea <= mid < eb),
                    default=None)
        names.append(inner[1] if inner else None)
    quiet = "(no program span)"
    by: dict = {}
    for ga, gb in trace.gaps():
        t = ga
        i = max(bisect.bisect_right(cuts, ga) - 1, 0)
        while t < gb:
            if not cuts or t < cuts[0] or i >= len(names):
                nxt = cuts[0] if cuts and t < cuts[0] else gb
                name = quiet
            else:
                nxt = cuts[i + 1]
                name = names[i] or quiet
                i += 1
            nxt = min(max(nxt, t), gb)
            if nxt == t:
                nxt = gb if i >= len(names) else nxt
            by[name] = by.get(name, 0.0) + (nxt - t) / 1e9
            t = nxt
    n_dev = max(len(trace.devices), 1)
    return sorted(([n, s / n_dev] for n, s in by.items()),
                  key=lambda p: -p[1])[:k]
