"""repro.observe — telemetry: trackers, spans, instruments, perf trend.

Four legs, all on the host side:

* **Trackers** (PR 7): a tracker is anything with
  ``log_metrics(step, metrics)`` (levanter-style). The estimator feeds
  it per-level cascade statistics (KKT residual, objective,
  support-vector count, rows/s) and per-segment DSVRG progress.
* **Spans**: ``span(name, **attrs)`` times host-side regions —
  fit → route → cascade level / stream pass, request batch → score —
  and ``trace_ctx(dir)`` exports them as Chrome-trace/Perfetto JSON.
  Each span records its ``id`` and its ``parent`` (the innermost span
  open on its thread, or, for work wrapped by ``bind``, on the thread
  that submitted it); ``Span.set`` adds attributes at its end. While a
  recorder is installed each span is also a ``jax.profiler``
  annotation, so a profiler trace shows the spans on its own clock, and
  a ``jax.monitoring`` listener (registered by the first install)
  records JAX's tracing, lowering, compiling and cache loads as
  ``compile.*`` spans. With no recorder installed nothing runs: no
  span, annotation, listener or clock read.
* **Instruments** (PR 9): counters, gauges, and fixed-bucket histograms
  with exact nearest-rank p50/p95/p99; ``MetricsRegistry`` is itself a
  tracker and drains back through any tracker backend.
* **Trend** (PR 9): :mod:`repro.observe.trend` compares a directory of
  ``BENCH_*.json`` records against committed baselines;
  ``scripts/bench_gate.py`` turns that into a CI perf gate.
"""
from repro.observe.tracker import (
    CompositeTracker,
    InMemoryTracker,
    JsonlTracker,
    Tracker,
    read_jsonl,
)
from repro.observe.profiler import profile_ctx
from repro.observe.spans import (
    Span,
    SpanRecorder,
    bind,
    current_recorder,
    install,
    span,
    trace_ctx,
)
from repro.observe.instruments import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
)
from repro.observe import trend

__all__ = [
    "Tracker",
    "InMemoryTracker",
    "JsonlTracker",
    "CompositeTracker",
    "read_jsonl",
    "profile_ctx",
    "Span",
    "SpanRecorder",
    "span",
    "bind",
    "trace_ctx",
    "install",
    "current_recorder",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "percentile",
    "DEFAULT_BUCKETS",
    "trend",
]
