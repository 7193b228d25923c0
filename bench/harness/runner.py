"""One run of one cell: set up, measure a window, check, report.

The order is fixed by what each step may touch. Set-up (data, weights,
compilation or cache loads, warm-up) ends where the window opens; the
window drives the program and nothing else; then the device's peak memory
is read, the program's answers are collected, its state is freed, and
only then does the plain reference run and the comparison decide
``correct``.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

from harness import cells, peaks, trace as trace_mod

# JAX times every compile request under this event, cache loads included;
# a persistent-cache hit also records CACHE_HIT
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Readings:
    """What a per-layer reader may read: the program's host spans, its
    counters, the device trace, the chip's peaks and the cell."""

    def __init__(self, cell, spans, counters, trace, host_t0_s, peak):
        self.notes: list = []       # lines for standard error
        self.cell = cell
        self.spans = spans
        self.counters = counters
        self.trace = trace
        self.host_t0_s = host_t0_s
        self.peak = peak

    def spans_named(self, name: str) -> list:
        return [e for e in self.spans if e["name"] == name]


class _CompileCount:
    """Compile requests and persistent-cache hits; their difference is
    what XLA actually compiled."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.requests += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.requests - self.hits, self.hits


_COMPILES: list = []


def compile_count() -> _CompileCount:
    """The process's one counter of compilations (listeners, once made,
    stay registered, so there is one for every caller)."""
    if not _COMPILES:
        import jax
        _COMPILES.append(_CompileCount(jax))
    return _COMPILES[0]


def require_chips(jax, chips: int):
    """The devices of the cell; exits non-zero, printing no result, where
    JAX finds no accelerator or fewer chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        sys.exit(f"bench: JAX found no accelerator (first device: "
                 f"{devs[0].platform} {devs[0].device_kind}); nothing ran")
    if len(devs) < chips:
        sys.exit(f"bench: the cell asks for {chips} chips, JAX sees "
                 f"{len(devs)}; nothing ran")
    return devs[:chips]


def _peak_bytes(devices) -> int:
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


@contextlib.contextmanager
def _profiled(jax, trace_dir):
    if trace_dir is None:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # Python calls would swamp the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run(cell, seed: int, seconds: float, traced: bool, *, t_start: float,
        devices, trace_dir: Path | None = None, log=sys.stderr,
        driver_cls=None) -> dict:
    """Run ``cell`` and return the result object (see ``bench/run.py``)."""
    import jax
    from repro.observe import install, SpanRecorder

    compiles = compile_count()
    if driver_cls is None:
        driver_cls = cells.load_module("drivers", cell.driver).Driver
    drv = driver_cls(cell, seed, devices, log=log)
    drv.setup()

    rec = SpanRecorder() if traced else None
    with _profiled(jax, trace_dir if traced else None):
        with (install(rec) if traced else contextlib.nullcontext()):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                t_w0 = time.perf_counter()
                c0, h0 = compiles.snapshot()
                drv.window(seconds)
                t_w1 = time.perf_counter()
                c1, h1 = compiles.snapshot()
    setup_s = t_w0 - t_start
    print(f"window {t_w1 - t_w0:.3f} s; programs compiled inside the "
          f"window: {c1 - c0} (and {h1 - h0} loaded from the persistent "
          f"cache)", file=log, flush=True)
    peak_bytes = _peak_bytes(devices)
    counters = drv.counters()
    metrics: dict = {}
    breakdown = None
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    if traced:
        tr = trace_mod.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)   # hundreds of MB
        peak = peaks.of(dev0.device_kind) if dev0.platform != "cpu" \
            else None
        r = Readings(cell, rec.events(), counters, tr, t_w0, peak)
        for m in cell.per_layer:
            v = cells.load_module("metrics", m["name"]).read(r)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        for line in r.notes:
            print(line, file=log, flush=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": trace_mod.name_gaps(tr, rec.events(),
                                                      t_w0, 10)}
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    for line in drv.notes():
        print(line, file=log, flush=True)
    answers = drv.answers()
    drv.free()
    checks = drv.check(answers)
    attempted, failed = drv.attempted(), drv.failed()
    correct = bool(checks) and all(c.ok for c in checks) and attempted > 0 \
        and failed == 0
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    print(f"setup_s {setup_s!r}", file=log, flush=True)
    for c in checks:
        print(c.line(), file=log, flush=True)
    return out


def main(argv=None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    cell = cells.load(args.workload)

    import jax
    from repro import compile_cache
    compile_cache.enable()
    devices = require_chips(jax, cell.chips)
    trace_dir = cells.BENCH / ".traces" / cell.name
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start, devices=devices, trace_dir=trace_dir)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


def cache_env(root: Path) -> None:
    """Fix JAX's persistent compilation cache inside the checkout, before
    JAX is imported: the program's own default directory
    (``repro.compile_cache``), whatever the environment says, and every
    program cached however fast it compiled, so a cell's second run in a
    checkout compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
