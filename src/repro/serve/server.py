"""Microbatching throughput scorer for compiled ODM models.

Three layers, composable:

* :class:`MicrobatchScorer` — pads every request batch up to a fixed
  bucket ladder (powers of two by default) so the jit cache is bounded by
  ``len(buckets)`` however many distinct batch sizes traffic produces;
  batches above the top bucket are chunked. ``compiles`` exposes the
  bucket-trace count the tests pin.
* :class:`Batcher` — a deadline microbatcher: requests queue until either
  ``max_batch`` are waiting or the oldest has waited ``max_wait`` seconds,
  then the whole batch is scored in one scorer call. Time is injected
  (``now`` arguments) so tests and replay drivers are deterministic;
  :func:`serve_stream` replays an (arrival_time, x) trace through it and
  reports latency/throughput stats.
* :func:`score_sharded` — slabs a large SV set across the mesh's data
  axis inside ``shard_map``: every device scores the full request batch
  against its local slab of the expansion and a ``psum`` adds the partial
  scores (the decision function is linear in the SV slab). O(S/n_dev)
  model memory per device; linear-collapse models short-circuit to the
  replicated O(d) matvec.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.observe.instruments import MetricsRegistry, percentile
from repro.observe.spans import span as _span
from repro.precision import matmul
from repro.serve.model import FittedODM

Array = jax.Array


def _bucket_ladder(max_batch: int) -> tuple[int, ...]:
    """1, 2, 4, ... up to (and including) max_batch."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


class MicrobatchScorer:
    """Bucket-padded scoring with a bounded jit cache.

    ONE jitted score function per scorer, taking the model arrays as
    *arguments* (not closed-over constants — baking the SV slab into
    every bucket's executable would duplicate a potentially multi-MB slab
    ladder-many times). jit's cache is keyed by the request shape, and
    every request is padded onto the bucket ladder, so the number of
    traces stays <= len(buckets) however many batch sizes traffic sees.
    """

    def __init__(self, model: FittedODM, max_batch: int = 256,
                 buckets: tuple[int, ...] | None = None,
                 metrics: MetricsRegistry | None = None):
        self.model = model
        self.metrics = metrics
        self.buckets = tuple(sorted(buckets or _bucket_ladder(max_batch)))
        self.max_batch = self.buckets[-1]
        self.calls = 0
        self._seen: set[int] = set()
        if model.w is not None:
            self._score = jax.jit(matmul)
            self._margs = (model.w,)
        else:
            spec = model.spec

            def scores(xb, z, c):
                from repro.kernels import ops
                return ops.decision_scores(xb, z, c, spec)

            self._score = jax.jit(scores)
            self._margs = (model.x_sv, model.coef)

    @property
    def compiles(self) -> int:
        """Distinct bucket shapes traced so far (<= len(buckets) always)."""
        return len(self._seen)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def score(self, x: Array) -> Array:
        """Decision scores (B,) for any batch size; pads to the bucket,
        chunks batches above the top bucket."""
        B = x.shape[0]
        self.calls += 1
        if B == 0:
            return jnp.zeros((0,), x.dtype)
        t0 = time.perf_counter()
        with _span("serve.score", batch=B):
            outs = []
            off = 0
            while off < B:
                n = min(B - off, self.max_batch)
                bucket = self._bucket_for(n)
                self._seen.add(bucket)
                xb = x[off:off + n]
                if n < bucket:
                    xb = jnp.pad(xb, ((0, bucket - n), (0, 0)))
                o = self._score(xb, *self._margs)
                outs.append(o if n == bucket else o[:n])
                off += n
            out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        if self.metrics is not None:
            self.metrics.counter("serve.score.calls").inc()
            self.metrics.histogram("serve.score.wall_s").observe(
                time.perf_counter() - t0)
            self.metrics.histogram("serve.score.batch").observe(B)
        return out

    def predict(self, x: Array) -> Array:
        return jnp.sign(self.score(x))


# ---------------------------------------------------------------------------
# deadline microbatcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    rid: int
    x: Array            # (d,)
    t_arrival: float


@dataclasses.dataclass
class Completed:
    rid: int
    score: float
    t_arrival: float
    t_done: float

    @property
    def latency(self) -> float:
        return self.t_done - self.t_arrival


class Batcher:
    """Queue/deadline microbatcher over a :class:`MicrobatchScorer`.

    ``submit`` enqueues one request; ``poll(now)`` flushes when the batch
    is full or the oldest request has waited past the deadline. All
    clocks are explicit arguments (``time.monotonic()`` by default) so
    replay is deterministic.
    """

    def __init__(self, scorer: MicrobatchScorer, max_batch: int = 64,
                 max_wait: float = 2e-3, faults=None,
                 metrics: MetricsRegistry | None = None):
        self.scorer = scorer
        self.max_batch = min(max_batch, scorer.max_batch)
        self.max_wait = max_wait
        # instrument registry (repro.observe.MetricsRegistry): per-request
        # latency + queue-wait histograms, queue-depth gauge, request /
        # batch counters. None (default) records nothing.
        self.metrics = metrics
        # fault-injection hook (repro.distributed.faults.FaultPlan): the
        # "serve.flush" site fires before scoring; with a virtual-clock
        # plan (sleeper=None) an injected delay shifts the batch's
        # completion time instead of wall-sleeping, so replay stays
        # deterministic
        self.faults = faults
        self._pending: list[_Pending] = []
        self._next_rid = 0
        self.batches: list[int] = []          # flushed batch sizes

    def submit(self, x: Array, now: float | None = None) -> int:
        now = time.monotonic() if now is None else now
        rid = self._next_rid
        self._next_rid += 1
        self._pending.append(_Pending(rid, x, now))
        if self.metrics is not None:
            self.metrics.counter("serve.requests").inc()
            self.metrics.gauge("serve.queue_depth").set(len(self._pending))
        return rid

    def ready(self, now: float | None = None) -> bool:
        if not self._pending:
            return False
        if len(self._pending) >= self.max_batch:
            return True
        now = time.monotonic() if now is None else now
        return now - self._pending[0].t_arrival >= self.max_wait

    def flush(self, now: float | None = None) -> list[Completed]:
        """Score everything pending (at most max_batch) in ONE call."""
        if not self._pending:
            return []
        now = time.monotonic() if now is None else now
        batch, self._pending = (self._pending[:self.max_batch],
                                self._pending[self.max_batch:])
        if self.faults is not None:
            now += self.faults.site("serve.flush", batch=len(batch))
        with _span("serve.request_batch", batch=len(batch)):
            xb = jnp.stack([p.x for p in batch])
            scores = jax.device_get(self.scorer.score(xb))
        self.batches.append(len(batch))
        done = [Completed(p.rid, float(s), p.t_arrival, now)
                for p, s in zip(batch, scores)]
        if self.metrics is not None:
            self.metrics.counter("serve.batches").inc()
            self.metrics.gauge("serve.queue_depth").set(len(self._pending))
            lat_h = self.metrics.histogram("serve.request.latency_s")
            for c in done:
                lat_h.observe(c.latency)
        return done

    def poll(self, now: float | None = None) -> list[Completed]:
        now = time.monotonic() if now is None else now
        out: list[Completed] = []
        while self.ready(now):
            out.extend(self.flush(now))
        return out


def serve_stream(batcher: Batcher, arrivals, *, tick: float | None = None
                 ) -> dict:
    """Replay an iterable of (t_arrival, x) events through the batcher.

    Virtual-clock replay: requests are submitted in arrival order and the
    batcher is polled at each arrival plus one final deadline tick, so
    results are independent of host timing. Returns
    {results, latencies, batches, mean_batch, p50, p95, p99} — the
    percentiles are exact nearest-rank (:func:`repro.observe.percentile`,
    shared with the observe histograms; the old ``lat[n // 2]`` indexing
    over-reported at even/small n).
    """
    results: list[Completed] = []
    t_last = 0.0
    for t, x in arrivals:
        results.extend(batcher.poll(t))
        batcher.submit(x, t)
        t_last = max(t_last, t)
    results.extend(batcher.poll(t_last + batcher.max_wait))
    lat = sorted(r.latency for r in results)
    n = len(lat)
    return {
        "results": results,
        "latencies": lat,
        "batches": list(batcher.batches),
        "mean_batch": (sum(batcher.batches) / len(batcher.batches)
                       if batcher.batches else 0.0),
        "p50": percentile(lat, 50) if n else 0.0,
        "p95": percentile(lat, 95) if n else 0.0,
        "p99": percentile(lat, 99) if n else 0.0,
    }


# ---------------------------------------------------------------------------
# SPMD: SV slab sharded across the mesh
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_scorer(mesh: jax.sharding.Mesh, data_axis: str, spec):
    """One jit(shard_map) per (mesh, axis, kernel spec) — jit's own cache
    handles the (request, slab) shapes, so repeated serving calls never
    retrace."""
    def body(xb, zs, cs):
        from repro.kernels import ops
        part = ops.decision_scores(xb, zs, cs, spec)
        return jax.lax.psum(part, data_axis)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis)),
        out_specs=P(),
        check_vma=False,
    ))


# padded + device-sharded SV slabs, one per (model slab, mesh, axis):
# re-padding and re-sharding O(S·d) bytes per request batch would defeat
# the O(S/n_dev)-per-device goal. Weakref-keyed (liveness proves the id)
# and FIFO-capped like the sodm predict cache.
_SLAB_CACHE: dict = {}
_SLAB_CACHE_CAP = 8


def _sharded_slab(model: FittedODM, mesh: jax.sharding.Mesh,
                  data_axis: str):
    import weakref
    from jax.sharding import NamedSharding

    key = (id(model.x_sv), mesh, data_axis)
    hit = _SLAB_CACHE.get(key)
    if hit is not None and hit[0]() is model.x_sv:
        return hit[1], hit[2]
    n_dev = mesh.shape[data_axis]
    pad = -model.n_sv % n_dev
    z = jnp.pad(model.x_sv, ((0, pad), (0, 0)))
    c = jnp.pad(model.coef, (0, pad))
    z = jax.device_put(z, NamedSharding(mesh, P(data_axis)))
    c = jax.device_put(c, NamedSharding(mesh, P(data_axis)))
    if len(_SLAB_CACHE) >= _SLAB_CACHE_CAP:
        _SLAB_CACHE.pop(next(iter(_SLAB_CACHE)))
    _SLAB_CACHE[key] = (weakref.ref(model.x_sv), z, c)
    return z, c


def score_sharded(model: FittedODM, x: Array, mesh: jax.sharding.Mesh,
                  data_axis: str = "data") -> Array:
    """Decision scores with the SV slab sharded over ``mesh[data_axis]``.

    The expansion is linear in the SVs, so each device scores the
    (replicated) request batch against its local slab and one ``psum``
    assembles f. The slab is padded to a device multiple with zero
    coefficients (zero coef rows contribute exactly nothing), device_put
    with the data-axis sharding ONCE per (model, mesh), and the
    jit(shard_map) is built once per (mesh, axis, spec) — repeat calls
    pay only the scoring. Linear models score replicated — the w matvec
    is already O(d).
    """
    if model.w is not None:
        return matmul(x, model.w)

    z, c = _sharded_slab(model, mesh, data_axis)
    return _sharded_scorer(mesh, data_axis, model.spec)(x, z, c)
