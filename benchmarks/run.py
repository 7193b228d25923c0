"""Benchmark driver: one module per paper table/figure + kernel micro +
roofline aggregation. Prints CSV-ish lines; `python -m benchmarks.run`.

Select subsets: `python -m benchmarks.run table2 fig4`.
Flags: `--quick` routes each bench through its toy-scale path;
`--out-dir DIR` additionally persists one ``BENCH_<name>.json`` per bench
(schema below) so CI runs leave a machine-readable trail instead of only
scrollback.

Persisted schema (schema_version 2):

    {"schema_version": 2, "bench": "<name>", "device_kind": "...",
     "backend": "cpu|gpu|tpu", "jax_version": "...",
     "wall_clock_s": 1.23, "peak_bytes": 0-or-device-peak,
     "rows": <len(lines)>, "lines": ["table2,...", ...],
     "metrics": {"serve.request.latency_s.p99": ..., ...}}

``peak_bytes`` is ``device.memory_stats()["peak_bytes_in_use"]``; it is 0
only on the CPU, which keeps no such stat — on any other backend a
missing stat is an error, not a 0. ``metrics`` (new in schema 2) is whatever
flat instrument snapshot the bench's ``run()`` returns — a
``repro.observe.MetricsRegistry.snapshot()`` dict of histogram
percentiles / counters — or ``{}`` for benches that return nothing.
``repro.observe.trend`` + ``scripts/bench_gate.py`` consume these
records and compare them against ``benchmarks/baselines/``.
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import (data_bench, fig2_speedup, fig4_gradient,
                        kernels_bench, roofline_report, serve_bench,
                        table2_rbf, table3_linear, table4_svm)

ALL = {
    "table2": table2_rbf.run,
    "table3": table3_linear.run,
    "table4": table4_svm.run,
    "fig2": fig2_speedup.run,
    "fig4": fig4_gradient.run,
    "kernels": kernels_bench.run,
    "roofline": roofline_report.run,
    "serve": serve_bench.run,
    "data": data_bench.run,
}

# how each bench spells "toy scale" (run() signatures differ)
_QUICK_KW = {
    "table2": {"datasets": ["svmguide1"], "scale_factor": 0.1},
    "table3": {"datasets": ["svmguide1"], "scale_factor": 0.1},
    "fig2": {"quick": True},
    "fig4": {"datasets": [("a7a", 0.01)]},
    "kernels": {"quick": True},
    "serve": {"quick": True},
    "data": {"quick": True},
}


def _peak_bytes() -> int:
    """Device peak memory; 0 only on the CPU, which keeps no such stat.
    Any other backend must report it (a failure there raises)."""
    import jax
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return 0
    return int(dev.memory_stats()["peak_bytes_in_use"])


def _persist(out_dir: str, name: str, lines: list[str],
             wall_s: float, metrics: dict | None = None) -> str:
    import jax
    dev = jax.local_devices()[0]
    record = {
        "schema_version": 2,
        "bench": name,
        "device_kind": dev.device_kind,
        "backend": dev.platform,
        "jax_version": jax.__version__,
        "wall_clock_s": round(wall_s, 4),
        "peak_bytes": _peak_bytes(),
        "rows": len(lines),
        "lines": list(lines),
        "metrics": dict(metrics) if isinstance(metrics, dict) else {},
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    quick = False
    out_dir = None
    picks: list[str] = []
    it = iter(argv)
    for a in it:
        if a == "--quick":
            quick = True
        elif a == "--out-dir":
            out_dir = next(it, None)
            if out_dir is None:
                print("--out-dir needs a directory argument")
                return 1
        else:
            picks.append(a)
    picks = picks or list(ALL)

    out: list[str] = []
    for name in picks:
        if name not in ALL:
            print(f"unknown benchmark {name!r}; options: {list(ALL)}")
            return 1
        kw = _QUICK_KW.get(name, {}) if quick else {}
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        ret = ALL[name](out, **kw)
        wall = time.time() - t0
        for line in out:
            print(line, flush=True)
        if out_dir is not None:
            print(f"wrote {_persist(out_dir, name, out, wall, ret)}",
                  flush=True)
        print(f"=== {name} done in {wall:.1f}s ===", flush=True)
        out.clear()
    return 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
