"""Hierarchical span tracing exported as Chrome-trace / Perfetto JSON.

``span(name, **attrs)`` is a context manager that times a host-side
region of the training or serving path and records it as a complete
("ph": "X") Chrome trace event with its thread id and microsecond
(ts, dur) on the host's ``perf_counter`` clock.

Parent links: every recorded span carries, under its event's ``args``,
an ``id`` and the ``parent`` id of the innermost span open on its thread
when it began (a thread-local stack, touched only while a recorder is
installed). Work handed to another thread keeps its cause: a function
wrapped by :func:`bind` runs under the submitter's current span, so the
prefetch thread's ``data.shard`` reads name the ``dsvrg.pass`` that
asked for them. ``Span.set(**attrs)`` adds attributes known only at the
end of the region (a level's pass count, a pass's slab counters).

One clock with the device trace: while a recorder is installed, each
span also opens a ``jax.profiler.TraceAnnotation`` of the same name, so
a ``jax.profiler`` trace taken meanwhile (``ODMEstimator.fit(...,
profile_dir=...)``) shows the program's spans on the host plane of its
``.xplane.pb``, on the profiler's own clock. The recorder's events keep
their ``perf_counter`` timestamps.

Compile spans: the first :class:`install` registers one
``jax.monitoring`` duration listener, which records ``compile.trace``,
``compile.lower``, ``compile.backend`` (persistent-cache loads included)
and ``compile.cache_load`` spans for the compile-path events JAX
reports, each ending when JAX reports it and lasting the duration JAX
gives, with the innermost open span of the compiling thread as parent.
It returns at once while no recorder is installed.

Zero cost when off: with no recorder installed, ``span()`` returns a
shared no-op context manager — no allocation beyond the call, no
timestamps, no locks, no annotation — and a process that never installs
a recorder registers no listener, so production paths keep the
instrumentation inline unconditionally. The recorder is installed
process-wide (:func:`trace_ctx` / :func:`install`) rather than
thread-locally because instrumented regions span worker threads (the
prefetch reads, the straggler scheduler's partition attempts, the
checkpoint writer).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time

__all__ = ["Span", "SpanRecorder", "span", "bind", "trace_ctx", "install",
           "current_recorder"]


class _NoopSpan:
    """Shared do-nothing context manager returned when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        del attrs


_NOOP = _NoopSpan()

#: the process-wide recorder; None means tracing is off (the fast path)
_ACTIVE: "SpanRecorder | None" = None

#: per thread, the ids of the spans open on it, innermost last
_LOCAL = threading.local()
_IDS = itertools.count(1)

#: ``jax.profiler.TraceAnnotation``, set by the first install
_ANNOTATION = None
_ARM_LOCK = threading.Lock()

#: JAX's compile-path duration events and the spans they become
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}


def _open_ids() -> list:
    try:
        return _LOCAL.ids
    except AttributeError:
        _LOCAL.ids = []
        return _LOCAL.ids


def _close(ids: list, span_id: int) -> None:
    """Take the innermost ``span_id`` off a thread's stack (the top one,
    unless spans were closed out of order)."""
    for i in range(len(ids) - 1, -1, -1):
        if ids[i] == span_id:
            del ids[i]
            return


class Span:
    """One in-flight span; records itself into the recorder on exit."""

    __slots__ = ("recorder", "name", "attrs", "t0", "id", "parent",
                 "_annotation")

    def __init__(self, recorder: "SpanRecorder", name: str, attrs: dict):
        self.recorder = recorder
        self.name = name
        self.attrs = attrs
        self.t0 = 0
        self.id = next(_IDS)
        self.parent = None
        self._annotation = None

    def set(self, **attrs) -> None:
        """Add attributes to the span's event (known only at its end)."""
        self.attrs.update(attrs)

    def __enter__(self):
        ids = _open_ids()
        self.parent = ids[-1] if ids else None
        ids.append(self.id)
        if _ANNOTATION is not None:
            self._annotation = _ANNOTATION(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _close(_open_ids(), self.id)
        self.recorder.add_span(self.name, self.t0 / 1e3,
                               (t1 - self.t0) / 1e3,
                               tid=threading.get_ident(),
                               **{"id": self.id, "parent": self.parent,
                                  **self.attrs})
        return False


class SpanRecorder:
    """Collects finished spans as Chrome trace events (thread-safe)."""

    def __init__(self):
        self._events: list[dict] = []
        self._lock = threading.Lock()

    def add_span(self, name: str, ts_us: float, dur_us: float, *,
                 tid: int | str = 0, **attrs) -> None:
        """Append one complete event. ``ts_us``/``dur_us`` are
        microseconds on any monotonic clock base (real spans use
        ``perf_counter``; virtual-clock replays may supply their own)."""
        event = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
                 "pid": os.getpid(), "tid": tid}
        if attrs:
            event["args"] = {k: _jsonable(v) for k, v in attrs.items()}
        with self._lock:
            self._events.append(event)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def spans(self, name: str | None = None) -> list[dict]:
        """Recorded events, optionally filtered by span name."""
        evs = self.events()
        return evs if name is None else [e for e in evs
                                         if e["name"] == name]

    def to_chrome_trace(self) -> dict:
        """The Chrome trace JSON object (load in Perfetto / about:tracing)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str | os.PathLike) -> str:
        """Write the trace JSON; parent directories are created."""
        path = os.fspath(path)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool, type(None))):
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(e) for e in v]
    try:
        return float(v)            # jnp/np scalars
    except (TypeError, ValueError):
        return repr(v)


def span(name: str, **attrs):
    """Time a host-side region when a recorder is installed; otherwise a
    shared no-op (the zero-cost-when-off contract)."""
    rec = _ACTIVE
    if rec is None:
        return _NOOP
    return Span(rec, name, attrs)


def bind(fn):
    """``fn``, made to run under the calling thread's innermost open span,
    on whatever thread calls it: spans it opens name that span as their
    parent. Returns ``fn`` itself when no recorder is installed or no
    span is open."""
    if _ACTIVE is None:
        return fn
    ids = _open_ids()
    if not ids:
        return fn
    parent = ids[-1]

    def under_parent(*args, **kwargs):
        ids = _open_ids()
        ids.append(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            _close(ids, parent)

    return under_parent


def _on_duration(event: str, duration: float, **kwargs) -> None:
    """The ``jax.monitoring`` listener: a compile-path event becomes a span
    ending now and lasting ``duration`` seconds."""
    rec = _ACTIVE
    if rec is None:
        return
    name = COMPILE_EVENTS.get(event)
    if name is None:
        return
    t1 = time.perf_counter_ns() / 1e3
    dur = duration * 1e6
    ids = _open_ids()
    attrs = {"id": next(_IDS), "parent": ids[-1] if ids else None}
    if "fun_name" in kwargs:
        attrs["fun"] = kwargs["fun_name"]
    rec.add_span(name, t1 - dur, dur, tid=threading.get_ident(), **attrs)


def _arm() -> None:
    """On the first install: take ``TraceAnnotation`` and register the
    compile listener, once per process."""
    global _ANNOTATION
    if _ANNOTATION is not None:
        return
    with _ARM_LOCK:
        if _ANNOTATION is not None:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _ANNOTATION = jax.profiler.TraceAnnotation


def current_recorder() -> SpanRecorder | None:
    return _ACTIVE


class install:
    """Install ``recorder`` process-wide for the ``with`` block.

    Re-entrant in the stacking sense: the previous recorder (usually
    None) is restored on exit, so an outer fit trace survives an inner
    scoped one.
    """

    def __init__(self, recorder: SpanRecorder):
        self.recorder = recorder
        self._prev: SpanRecorder | None = None

    def __enter__(self) -> SpanRecorder:
        global _ACTIVE
        _arm()
        self._prev = _ACTIVE
        _ACTIVE = self.recorder
        return self.recorder

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


class trace_ctx:
    """Record spans for the block and export ``<trace_dir>/trace.json``.

    No-op when ``trace_dir`` is None (mirrors ``profile_ctx``), so call
    sites can take a ``trace_dir=`` kwarg without branching. The export
    happens even if the block raises — a preempted fit still leaves its
    partial trace on disk.
    """

    FILENAME = "trace.json"

    def __init__(self, trace_dir: str | os.PathLike | None):
        self.trace_dir = trace_dir
        self.recorder: SpanRecorder | None = None
        self._install: install | None = None

    def __enter__(self) -> SpanRecorder | None:
        if self.trace_dir is None:
            return None
        self.recorder = SpanRecorder()
        self._install = install(self.recorder)
        self._install.__enter__()
        return self.recorder

    def __exit__(self, *exc):
        if self._install is not None:
            self._install.__exit__(*exc)
            self.recorder.export(
                os.path.join(os.fspath(self.trace_dir), self.FILENAME))
        return False
