"""DSVRG for linear-kernel ODM (paper Algorithm 2, after Lee et al. 2017).

Per epoch:
  1. every node computes the sum of per-instance gradients on its partition;
     one all-reduce produces the full gradient h (the only O(d)
     communication of the epoch besides the iterate hand-off);
  2. nodes run SVRG inner updates
         w <- w - eta * (grad_i(w) - grad_i(w_anchor) + h)
     serially in a round-robin, each consuming its local auxiliary samples
     without replacement and passing w to the next node.

Execution model (the jitted epoch-scan driver): both :func:`_solve` and
:func:`_solve_sharded` run ALL epochs inside one ``lax.scan`` — each config
traces exactly once (pinned by ``epoch_trace_count`` in the test battery),
the iterate w never round-trips to host between epochs, the per-epoch
objective history is accumulated on device in the scan carry (the sharded
layout reduces it with a ``psum`` of local loss sums instead of
re-evaluating the full objective on host), and the ``auto_eta`` smoothness
step is computed inside the trace (a ``psum`` of E‖x‖² on the mesh) so
sharded and single-process solves always use the same step size. Every
partition is pre-sliced into ceil(m/batch) static minibatches with a
validity mask on the ragged tail, so each sample is consumed exactly once
per epoch (Alg. 2's without-replacement sampling) whatever the batch size.

The inner-step direction g_w − g_a + h is the hot spot; on TPU it runs as
ONE fused Pallas pass over the minibatch (margins for w AND the anchor as
a single MXU op, coefficient difference, back-projection — see
:mod:`repro.kernels.odm_grad`), with the pure-jnp form
(:func:`repro.core.odm.svrg_direction`) as the interpret-mode/CPU
reference (``DSVRGConfig.fused``).

Faithful mode (:func:`_solve`) reproduces the serial chain exactly with a
``lax.scan`` over nodes (inner scan over that node's minibatches). SPMD
mode (:func:`_solve_sharded`) keeps step 1 as a ``psum`` on the mesh and
offers two inner-phase schedules:

* ``schedule='serial'`` — the faithful round-robin. On an SPMD mesh every
  device executes the same chain over the all-gathered partitions
  (replicated compute, one slab gather per epoch); semantically identical
  to the paper, trivially correct.
* ``schedule='parallel'`` — beyond-paper: all K chains advance in parallel
  from the same anchor and are averaged at epoch end (local-SGD style).
  One extra O(d) all-reduce per epoch; K× less wall-clock per epoch. Lee
  et al.'s sampling-without-replacement analysis covers each chain; the
  averaging step is the standard local-update extension. EXPERIMENTS
  ablates both.

The objective/gradients are the primal ODM of Section 3.3 (see
repro.core.odm.{primal_objective, svrg_direction}).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import odm
from repro.core import partition as part_mod
from repro.core.odm import ODMParams
from repro.observe.spans import Span, span as _span
from repro.precision import matmul

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DSVRGConfig:
    n_partitions: int = 8
    n_landmarks: int = 8
    epochs: int = 10
    eta: float = 0.0                # <= 0: auto = 0.5 / L_hat (see auto_eta)
    batch: int = 1                  # inner minibatch size (1 = paper-faithful)
    schedule: str = "serial"        # serial | parallel
    partition_strategy: str = "stratified"
    fused: bool | None = None       # None: fused Pallas direction kernel when
    #                                 compiled (TPU), jnp reference under
    #                                 interpret mode / CPU
    coreset_frac: float = 0.1       # anchor-coreset fraction of the csvrg
    #                                 baseline route (ignored elsewhere)
    stream_slab: int = 4096         # rows per host->device slab on the
    #                                 streaming path (_solve_stream); rounded
    #                                 up to a multiple of ``batch``


def auto_eta(x: Array, params: ODMParams, frac: float = 0.5) -> float:
    """Step size from the smoothness of the per-instance objective:
    L_hat = 1 + s * E||x||^2 with s = lam/(1-theta)^2 (the Hessian of the
    quadratic-hinge term is bounded by s x xᵀ; the ridge adds 1).

    Host-side convenience; the solve drivers evaluate the identical
    formula inside the trace (sharded: psum of the local ‖x‖² sums), so a
    solve never pays a host round-trip for it.
    """
    return float(_eta_from_sumsq(jnp.sum(x * x), params, x.shape[0], frac))


def _eta_from_sumsq(sumsq: Array, params: ODMParams, M: int,
                    frac: float = 0.5) -> Array:
    s = params.lam / (1.0 - params.theta) ** 2
    return frac / (1.0 + s * sumsq / M)


class DSVRGResult(NamedTuple):
    w: Array
    history: Array      # (epochs,) primal objective after each epoch
    perm: Array
    eta: Array | float = 0.0   # step size actually used (auto or cfg.eta)


# ---------------------------------------------------------------------------
# trace accounting (compile-count pin for the scan drivers)
# ---------------------------------------------------------------------------

# one bump per jit trace of a solve driver (local, sharded, or a streamed
# slab program), its event naming the driver and its static config. The
# scan body itself is NOT counted — lax.scan legitimately retraces its body
# for abstract eval; what we pin is that a whole solve is one trace per
# config (routes.dsvrg.trace_once in repro.analysis.invariants).
from repro.analysis.invariants import counter as _inv_counter  # noqa: E402

_EPOCH_TRACE_COUNTER = _inv_counter("dsvrg.epoch_trace")


def epoch_trace_count() -> int:
    """How many times a DSVRG solve driver has been traced (not dispatched)."""
    return _EPOCH_TRACE_COUNTER.count


def _resolve_fused(cfg: DSVRGConfig) -> bool:
    if cfg.fused is not None:
        return cfg.fused
    from repro.kernels import ops
    return not ops._INTERPRET


# ---------------------------------------------------------------------------
# batched-epoch building blocks
# ---------------------------------------------------------------------------

def _pad_batches(xs: Array, ys: Array,
                 batch: int) -> tuple[Array, Array, Array]:
    """Pre-slice partitions into static minibatches with a ragged-tail mask.

    xs (K, m, d), ys (K, m) -> xs (K, S, b, d), ys (K, S, b), wts (S, b)
    with S = ceil(m / b); padded rows have x = 0, y = 0, weight 0, so every
    real sample is consumed exactly once per epoch and the tail step's mean
    divides by the true tail size.
    """
    K, m, d = xs.shape
    b = min(batch, m)
    S = -(-m // b)
    pad = S * b - m
    xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    ys = jnp.pad(ys, ((0, 0), (0, pad)))
    wts = (jnp.arange(S * b) < m).astype(xs.dtype).reshape(S, b)
    return xs.reshape(K, S, b, d), ys.reshape(K, S, b), wts


def _direction(w: Array, anchor: Array, h: Array, xb: Array, yb: Array,
               wb: Array, params: ODMParams, fused: bool) -> Array:
    """One inner step's g_w − g_a + h: fused Pallas pass or jnp reference."""
    if fused:
        from repro.kernels import ops
        return ops.svrg_grad(w, anchor, h, xb, yb, wb, lam=params.lam,
                             theta=params.theta, ups=params.ups)
    return odm.svrg_direction(w, anchor, h, xb, yb, params, wb=wb)


def _loss_grad(anchor: Array, xf: Array, yf: Array, params: ODMParams,
               M: int, fused: bool) -> Array:
    """Hinge part of the full gradient over (possibly padded) rows, scaled
    by the TRUE count M. Padded rows (x = 0, y = 0) contribute nothing.
    The caller adds the ridge term (the anchor itself) after any psum."""
    if fused:
        from repro.kernels import ops
        g = ops.odm_grad(anchor, xf, yf,
                         lam=params.lam * xf.shape[0] / M,
                         theta=params.theta, ups=params.ups)
    else:
        g = odm.primal_grad(anchor, xf, yf, params, total=M)
    return g - anchor


def _epoch_serial(w: Array, xs: Array, ys: Array, wts: Array, anchor: Array,
                  h: Array, eta: Array, params: ODMParams,
                  fused: bool) -> Array:
    """One faithful round-robin epoch. xs: (K, S, b, d) pre-sliced
    minibatches; wts (S, b) masks each step's ragged-tail padding."""

    def node_body(w, xk_yk):
        xk, yk = xk_yk

        def inner(w, sl):
            xb, yb, wb = sl
            return w - eta * _direction(w, anchor, h, xb, yb, wb, params,
                                        fused), None

        w, _ = jax.lax.scan(inner, w, (xk, yk, wts))
        return w, None

    w, _ = jax.lax.scan(node_body, w, (xs, ys))
    return w


def _epoch_parallel(w: Array, xs: Array, ys: Array, wts: Array,
                    anchor: Array, h: Array, eta: Array, params: ODMParams,
                    fused: bool) -> Array:
    """Beyond-paper: K independent chains from the same anchor, averaged."""

    def chain(xk, yk):
        def inner(wk, sl):
            xb, yb, wb = sl
            return wk - eta * _direction(wk, anchor, h, xb, yb, wb, params,
                                         fused), None

        wk, _ = jax.lax.scan(inner, w, (xk, yk, wts))
        return wk

    ws = jax.vmap(chain)(xs, ys)                     # (K, d)
    return jnp.mean(ws, axis=0)


def _flatten(xs: Array, ys: Array, wts: Array):
    """(K, S, b, *) batch layout -> flat padded rows + per-row weights."""
    K, S, b = ys.shape
    xf = xs.reshape(K * S * b, -1)
    yf = ys.reshape(K * S * b)
    wf = jnp.broadcast_to(wts[None], (K, S, b)).reshape(K * S * b)
    return xf, yf, wf


def _partition_perm(x: Array, cfg: DSVRGConfig, K: int,
                    key: jax.Array) -> Array:
    from repro.core import kernel_fns as kf
    M = x.shape[0]
    if cfg.partition_strategy == "identity":
        # stream-order chain: rows stay where they are. This is what the
        # streaming driver implicitly uses (it has no global perm), so
        # the dense-vs-streaming parity tests run the dense solver with
        # this strategy to make the two inner chains comparable.
        return jnp.arange(M)
    if cfg.partition_strategy == "stratified":
        # linear kernel: strata in input space (phi = identity)
        spec = kf.KernelSpec(name="linear")
        plan = part_mod.make_plan(spec, x, cfg.n_landmarks, K, key)
        return plan.perm
    return part_mod.random_partitions(M, K, key)


# ---------------------------------------------------------------------------
# single-process driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("params", "cfg", "M"))
def _run(w0: Array, xs: Array, ys: Array, wts: Array, *, params: ODMParams,
         cfg: DSVRGConfig, M: int):
    """All epochs of a single-process solve in one trace (lax.scan)."""
    _EPOCH_TRACE_COUNTER.bump(("local", cfg, M))
    fused = _resolve_fused(cfg)
    epoch_fn = _epoch_serial if cfg.schedule == "serial" else _epoch_parallel
    xf, yf, wf = _flatten(xs, ys, wts)
    if cfg.eta > 0:
        eta = jnp.asarray(cfg.eta, xs.dtype)
    else:
        eta = _eta_from_sumsq(jnp.sum(wf * jnp.sum(xf * xf, axis=-1)),
                              params, M).astype(xs.dtype)

    def epoch(w, _):
        anchor = w
        h = anchor + _loss_grad(anchor, xf, yf, params, M, fused)
        w = epoch_fn(w, xs, ys, wts, anchor, h, eta, params, fused)
        return w, odm.primal_objective(w, xf, yf, params, weights=wf,
                                       total=M)

    w, hist = jax.lax.scan(epoch, w0, None, length=cfg.epochs)
    return w, hist, eta


def _solve(x: Array, y: Array, params: ODMParams, cfg: DSVRGConfig,
           key: jax.Array, w0: Array | None = None, *, faults=None,
           tracker=None, resume=None) -> DSVRGResult:
    M, d = x.shape
    K = cfg.n_partitions
    if M % K != 0:
        raise ValueError(f"K={K} must divide M={M}")
    if cfg.schedule not in ("serial", "parallel"):
        raise ValueError(f"unknown schedule {cfg.schedule!r}")

    perm = _partition_perm(x, cfg, K, key)
    xp, yp = x[perm], y[perm]
    xs, ys, wts = _pad_batches(xp.reshape(K, M // K, d),
                               yp.reshape(K, M // K), cfg.batch)
    w0 = jnp.zeros(d, x.dtype) if w0 is None else w0
    if faults is None and tracker is None and resume is None:
        w, hist, eta = _run(w0, xs, ys, wts, params=params, cfg=cfg, M=M)
    else:
        def runner(w, n):
            return _run(w, xs, ys, wts, params=params,
                        cfg=dataclasses.replace(cfg, epochs=n), M=M)

        w, hist, eta = _segmented(runner, w0, cfg, M, perm=perm,
                                  faults=faults, tracker=tracker,
                                  resume=resume)
    return DSVRGResult(w=w, history=hist, perm=perm, eta=eta)


# ---------------------------------------------------------------------------
# streaming driver (out-of-core: consumes a ShardedSource slab by slab)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_stream_steps(params: ODMParams, batch: int, fused: bool):
    """The two jitted per-slab kernels of the streaming driver.

    ``stats(anchor, xf, yf, wf, M)`` — one slab's contribution to the
    full-gradient / objective / ‖x‖² reductions of an epoch's anchor
    pass (flat padded rows, scaled by the true global M so partials sum
    to the dense quantities).

    ``inner(w, anchor, h, eta, xs, ys, wts)`` — the SVRG inner chain
    over one slab's pre-sliced (C, b, ·) minibatches: exactly
    ``_epoch_serial``'s inner scan, except a fully-padded minibatch
    (weight-sum 0, which only the zero-padded final slab can produce)
    is masked to a no-op instead of stepping by ``w − anchor + h``.

    Cached per (params, batch, fused) with jit handling shapes. The
    streaming driver does not call them slab by slab: it calls the slab
    programs :func:`_make_slab_steps` builds from them, one call a slab,
    and each of these two traces once inside those (pinned via the
    ``dsvrg.epoch_trace`` counter, as the resident drivers' trace-once
    discipline).
    """

    @functools.partial(jax.jit, static_argnames=("M",))
    def stats(anchor, xf, yf, wf, *, M):
        _EPOCH_TRACE_COUNTER.bump(("stream.stats", params, batch, M))
        gpart = _loss_grad(anchor, xf, yf, params, M, fused)
        ridge = 0.5 * matmul(anchor, anchor)
        losspart = odm.primal_objective(anchor, xf, yf, params, weights=wf,
                                        total=M) - ridge
        sqpart = jnp.sum(wf * jnp.sum(xf * xf, axis=-1))
        return gpart, losspart, sqpart

    @jax.jit
    def inner(w, anchor, h, eta, xs, ys, wts):
        _EPOCH_TRACE_COUNTER.bump(("stream.inner", params, batch))

        def step(w, sl):
            xb, yb, wb = sl
            live = jnp.where(jnp.sum(wb) > 0.0, eta, jnp.zeros_like(eta))
            return w - live * _direction(w, anchor, h, xb, yb, wb, params,
                                         fused), None

        w, _ = jax.lax.scan(step, w, (xs, ys, wts))
        return w

    return stats, inner


@functools.lru_cache(maxsize=None)
def _make_slab_steps(stats, inner, R: int, C: int, M: int):
    """The streaming driver's two per-slab programs, one jitted call a slab.

    ``anchor_step(acc, anchor, x, y, n_valid) -> acc`` — ``stats`` on one
    ``(R, d)`` slab, added into the pass's accumulators; ``acc`` holds
    ``(g, loss, sq)`` as one ``(d + 2,)`` vector, since every output
    array of a call costs the host as much time as a small transfer.

    ``inner_step(w, anchor, h, eta, x, y, n_valid) -> w`` — ``inner`` on
    the slab cut into ``C`` minibatches of ``R // C`` rows.

    Both build the slab's validity weights inside the trace from
    ``n_valid``, a traced int32 scalar, so the ragged final slab runs the
    same program. Built from ``(stats, inner)`` and cached on those
    objects with ``(R, C, M)``, so each traces once per configuration and
    slab shape, and a fresh ``_make_stream_steps`` (after its
    ``cache_clear``) gets fresh slab programs.
    """
    b = R // C

    def weights(n_valid, dtype):
        return (jax.lax.iota(jnp.int32, R) < n_valid).astype(dtype)

    @jax.jit
    def anchor_step(acc, anchor, x, y, n_valid):
        _EPOCH_TRACE_COUNTER.bump(("stream.anchor_step", R, M))
        gp, lp, sp = stats(anchor, x, y, weights(n_valid, x.dtype), M=M)
        return acc + jnp.concatenate([gp, lp[None], sp[None]])

    @jax.jit
    def inner_step(w, anchor, h, eta, x, y, n_valid):
        _EPOCH_TRACE_COUNTER.bump(("stream.inner_step", R, C))
        d = x.shape[-1]
        return inner(w, anchor, h, eta, x.reshape(C, b, d), y.reshape(C, b),
                     weights(n_valid, x.dtype).reshape(C, b))

    return anchor_step, inner_step


def _solve_stream(source, params: ODMParams, cfg: DSVRGConfig,
                  key: jax.Array | None = None, w0: Array | None = None, *,
                  faults=None, tracker=None, resume=None, depth: int = 2,
                  executor=None, metrics=None, accountant=None
                  ) -> tuple[DSVRGResult, Array]:
    """Out-of-core DSVRG: epochs stream ``cfg.stream_slab``-row slabs
    from a :class:`repro.data.streaming.sources.ShardedSource` through
    the prefetch loader; the (M, d) matrix is never resident.

    Per epoch, two passes over the stream: an anchor pass accumulating
    the full gradient h (plus the previous iterate's objective and, on
    the very first pass, the ``auto_eta`` ‖x‖² sum), then the serial
    SVRG inner chain over the global minibatch sequence. Slab
    boundaries are global row indices (``iter_slabs``), so every
    reduction runs in a fixed order — the fitted ``w`` is bitwise
    invariant to how the source is sharded, and a kill/resume replay
    through :class:`~repro.distributed.resume.DsvrgResumeManager` is
    bitwise identical to the uninterrupted run. Relative to the
    resident solver this is the K=1 stream-order chain
    (``partition_strategy="identity"``); ``n_partitions`` /
    ``partition_strategy`` are ignored.

    Each slab is two ``jnp.asarray`` transfers and one call of a slab
    program of :func:`_make_slab_steps`: ``anchor_step`` on anchor and
    final passes, ``inner_step`` on inner passes. Each of the two traces
    once per configuration, so refits and the ragged final slab compile
    nothing.

    Each pass over the stream is one ``dsvrg.pass`` span (``kind``
    anchor, inner or final; ``epoch`` within this call), so a fit of E
    epochs records 2E + 1. Only a recorded pass clocks its slabs
    (:func:`_clocked_pass`); with no recorder the loop makes no timing
    call.

    Returns ``(result, kkt)`` with ``result.perm = None`` (a stream has
    no materialized permutation) and ``kkt = ‖∇p(w)‖∞`` from a terminal
    gradient pass — the primal-stationarity analogue of the dual
    routes' projected-gradient residual.
    """
    from repro.data.streaming import loader as stream_loader

    M, d = int(source.n_rows), int(source.n_features)
    if M <= 0:
        raise ValueError("streaming solve needs a non-empty source")
    if cfg.schedule != "serial":
        raise ValueError(
            "streaming DSVRG supports schedule='serial' only (the "
            "parallel schedule needs all K chains resident at once); "
            f"got {cfg.schedule!r}")
    del key                      # stream order is the partition order
    b = min(cfg.batch, M)
    R = -(-max(cfg.stream_slab, b) // b) * b      # slab rows, multiple of b
    C = R // b
    dtype = jnp.zeros(0, dtype=source.dtype).dtype
    anchor_step, inner_step = _make_slab_steps(
        *_make_stream_steps(params, b, _resolve_fused(cfg)), R, C, M)

    if metrics is None and tracker is not None:
        from repro.observe import MetricsRegistry
        metrics = MetricsRegistry()

    def slabs():
        return stream_loader.iter_slabs(
            source, R, depth=depth, executor=executor, metrics=metrics,
            faults=faults, accountant=accountant)

    # a full slab's count goes to the device once, not once a slab: a
    # Python scalar argument is a transfer of its own at every call
    full = jnp.asarray(R, jnp.int32)

    def stream_pass(kind: str, epoch: int, program, state, *consts):
        """One pass over the stream, one call of the jitted slab
        ``program`` a slab: ``state = program(state, *consts, x, y,
        n_valid)``, under a ``dsvrg.pass`` span that, when recorded, also
        carries the pass's slab counters and host seconds."""
        def step(state, x, y, n_valid):
            count = full if n_valid == R else jnp.asarray(n_valid, jnp.int32)
            return program(state, *consts, x, y, count)

        with _span("dsvrg.pass", kind=kind, epoch=epoch) as sp:
            if isinstance(sp, Span):
                return _clocked_pass(sp, slabs(), step, state)
            for slab in slabs():
                state = step(state, jnp.asarray(slab.x), jnp.asarray(slab.y),
                             slab.n_valid)
            return state

    def anchor_pass(anchor, kind: str, epoch: int):
        acc = stream_pass(kind, epoch, anchor_step,
                          jnp.zeros(d + 2, dtype), anchor)
        return acc[:d], acc[d], acc[d + 1]

    eta_box: list = [jnp.asarray(cfg.eta, dtype) if cfg.eta > 0 else None]
    kkt_box: list = [jnp.zeros((), dtype)]

    def runner(w, n):
        """n epochs from iterate w -> (w', hist_n, eta); the _segmented
        contract. History entry e is obj(w after epoch e), read off the
        next epoch's anchor pass (or a terminal pass for the last one) —
        the streamed anchor pass already evaluates the objective, so no
        extra scan is spent on history except at segment end."""
        if n <= 0:
            eta0 = eta_box[0] if eta_box[0] is not None \
                else jnp.zeros((), dtype)
            return w, jnp.zeros((0,), dtype), eta0
        hist = []
        for e in range(n):
            anchor = w
            g, loss, sq = anchor_pass(anchor, "anchor", e)
            if eta_box[0] is None:
                eta_box[0] = _eta_from_sumsq(sq, params, M).astype(dtype)
            if e > 0:
                hist.append(0.5 * matmul(anchor, anchor) + loss)
            h = anchor + g
            w = stream_pass("inner", e, inner_step, w, anchor, h, eta_box[0])
        g, loss, _ = anchor_pass(w, "final", n)
        hist.append(0.5 * matmul(w, w) + loss)
        kkt_box[0] = jnp.max(jnp.abs(w + g))
        return w, jnp.stack(hist), eta_box[0]

    w0 = jnp.zeros(d, dtype) if w0 is None else w0
    if faults is None and tracker is None and resume is None:
        w, hist, eta = runner(w0, cfg.epochs)
    else:
        w, hist, eta = _segmented(runner, w0, cfg, M,
                                  perm=jnp.zeros((0,), jnp.int32),
                                  faults=faults, tracker=tracker,
                                  resume=resume)
    if metrics is not None and tracker is not None:
        metrics.drain(tracker, step=cfg.epochs)
    return DSVRGResult(w=w, history=hist, perm=None, eta=eta), kkt_box[0]


def _clocked_pass(sp: Span, slabs, step, state):
    """The streamed pass loop of ``_solve_stream``, adding up over its slabs
    what the pass's span records at its end: ``slabs``, ``rows`` (valid
    rows), ``h2d_bytes``, ``steps`` (calls of ``step``, each one call of a
    jitted slab program), and the host seconds of the whole loop body —
    ``wait_s`` inside the slab iterator's ``next()`` (prefetch waits, the
    carry copies, the label check, the loader's shutdown at the end),
    ``h2d_s`` in the ``jnp.asarray`` transfers of the slab, and
    ``dispatch_s`` in ``step`` (the slab program's call)."""
    clock = time.perf_counter
    wait = h2d = dispatch = 0.0
    n = rows = nbytes = steps = 0
    t_next = clock()
    for slab in slabs:
        t0 = clock()
        x, y = jnp.asarray(slab.x), jnp.asarray(slab.y)
        t1 = clock()
        state = step(state, x, y, slab.n_valid)
        t_end = clock()
        wait += t0 - t_next
        h2d += t1 - t0
        dispatch += t_end - t1
        steps += 1
        n += 1
        rows += slab.n_valid
        nbytes += slab.x.nbytes + slab.y.nbytes
        t_next = t_end
    wait += clock() - t_next
    sp.set(slabs=n, rows=rows, h2d_bytes=nbytes, steps=steps, wait_s=wait,
           h2d_s=h2d, dispatch_s=dispatch)
    return state


# ---------------------------------------------------------------------------
# segmented epoch driver (the instrumented / resumable path)
# ---------------------------------------------------------------------------

def _segmented(runner, w0: Array, cfg: DSVRGConfig, M: int, *, perm: Array,
               faults=None, tracker=None, resume=None):
    """Run ``cfg.epochs`` as checkpointable segments of the epoch scan.

    ``runner(w, n) -> (w', hist_n, eta)`` executes ``n`` epochs from
    iterate ``w`` (one jitted scan per distinct segment length — the
    default single-scan path and its trace-once pin are untouched; this
    driver only exists when faults/tracker/resume are requested). SVRG
    re-anchors at every epoch start, so the iterate ``w`` alone restarts
    the next epoch exactly and splitting the scan never changes the math:
    a resumed run and an uninterrupted run of this driver are
    bit-identical by construction.

    Between segments: the ``"dsvrg.segment"`` fault site fires, the
    tracker logs ``(epoch, objective, throughput)``, and the resume
    manager checkpoints ``{w, history, perm} + {epoch, eta}`` (the
    ``(w, anchor, epoch)`` of the module docs — anchor coincides with
    ``w`` at the boundary).
    """
    w, done, hist = w0, 0, None
    eta = jnp.zeros((), w0.dtype)
    seg = resume.segment if resume is not None else 1
    if resume is not None:
        restored = resume.restore()
        if restored is not None:
            w, done, hist = restored.w, restored.epoch, restored.history
            eta = jnp.asarray(restored.eta, w.dtype)
    while done < cfg.epochs:
        if faults is not None:
            faults.site("dsvrg.segment", epoch=done)
        n = min(seg, cfg.epochs - done)
        t0 = time.perf_counter()
        with _span("dsvrg.segment", epoch=done, epochs=n):
            w, h, eta = runner(w, n)
        hist = h if hist is None else jnp.concatenate([hist, h])
        done += n
        if tracker is not None:
            jax.block_until_ready(w)
            wall = time.perf_counter() - t0
            tracker.log_metrics(done, {
                "route": "dsvrg", "epoch": done,
                "objective": float(h[-1]), "eta": float(eta),
                "wall_s": wall, "rows_per_s": n * M / max(wall, 1e-9)})
        if resume is not None:
            resume.save_segment(epoch=done, w=w, history=hist, perm=perm,
                                eta=eta)
    if hist is None:                   # epochs == 0 and nothing restored
        hist = jnp.zeros((0,), w.dtype)
    return w, hist, eta


# ---------------------------------------------------------------------------
# SPMD engine
# ---------------------------------------------------------------------------

def _gather_slab(xs: Array, ys: Array,
                 data_axis: str) -> tuple[Array, Array]:
    """All-gather the (K, S, b, ·) partition slab for the serial chain."""
    return (jax.lax.all_gather(xs, data_axis, tiled=True),
            jax.lax.all_gather(ys, data_axis, tiled=True))


def _sharded_eta(xs: Array, ys: Array, wts: Array, params: ODMParams,
                 cfg: DSVRGConfig, M: int, data_axis: str,
                 eta: float | None) -> Array:
    """Step size inside the shard_map body. Explicit eta wins; otherwise
    auto_eta from the *sharded* data — a psum of the local ‖x‖² sums, so
    every device (and the single-process driver) lands on the identical
    step size. This replaces the old hardcoded 0.05 fallback."""
    if eta is not None:
        return jnp.asarray(eta, xs.dtype)
    if cfg.eta > 0:
        return jnp.asarray(cfg.eta, xs.dtype)
    xf, _, wf = _flatten(xs, ys, wts)
    sumsq = jax.lax.psum(jnp.sum(wf * jnp.sum(xf * xf, axis=-1)), data_axis)
    return _eta_from_sumsq(sumsq, params, M).astype(xs.dtype)


def _sharded_epoch(w: Array, xs: Array, ys: Array, wts: Array, eta: Array,
                   params: ODMParams, cfg: DSVRGConfig, M: int,
                   data_axis: str, fused: bool,
                   gathered: tuple[Array, Array] | None = None
                   ) -> tuple[Array, Array]:
    """One epoch inside a shard_map body: (w, local slab) -> (w', obj).

    Step 1 (full gradient) is a psum — the paper's single center-node
    reduction. Step 2 follows cfg.schedule (see module docs). The returned
    objective is the GLOBAL primal objective, assembled on device from the
    psum of local loss sums plus one ridge term — no host re-evaluation.
    ``gathered`` lets the epoch-scan driver all-gather the (loop-
    invariant) serial-schedule slab ONCE outside the scan instead of once
    per epoch — XLA does not hoist collectives out of while loops.
    """
    anchor = w
    xf, yf, wf = _flatten(xs, ys, wts)
    g_local = _loss_grad(anchor, xf, yf, params, M, fused)
    h = jax.lax.psum(g_local, data_axis) + anchor

    if cfg.schedule == "parallel":
        wk = _epoch_parallel(w, xs, ys, wts, anchor, h, eta, params, fused)
        w = jax.lax.pmean(wk, data_axis)
    else:
        xg, yg = gathered if gathered is not None else \
            _gather_slab(xs, ys, data_axis)
        w = _epoch_serial(w, xg, yg, wts, anchor, h, eta, params, fused)

    ridge = 0.5 * matmul(w, w)
    loss_local = odm.primal_objective(w, xf, yf, params, weights=wf,
                                      total=M) - ridge
    obj = jax.lax.psum(loss_local, data_axis) + ridge
    return w, obj


@functools.lru_cache(maxsize=None)
def _make_sharded_run(mesh: jax.sharding.Mesh, params: ODMParams,
                      cfg: DSVRGConfig, M: int, data_axis: str):
    """jit(shard_map) over ALL epochs: (w0, xs, ys, wts) -> (w, hist, eta).

    Cached per (mesh, params, cfg, M, data_axis) so repeated solves reuse
    one trace; the epoch loop is a lax.scan with the on-device objective
    history in the scanned carry.
    """
    fused = _resolve_fused(cfg)

    def run(w0, xs, ys, wts):
        eta = _sharded_eta(xs, ys, wts, params, cfg, M, data_axis, None)
        # the serial chain consumes the full slab every epoch — gather it
        # once here, not once per scan iteration
        gathered = _gather_slab(xs, ys, data_axis) \
            if cfg.schedule == "serial" else None

        def epoch(w, _):
            return _sharded_epoch(w, xs, ys, wts, eta, params, cfg, M,
                                  data_axis, fused, gathered=gathered)

        w, hist = jax.lax.scan(epoch, w0, None, length=cfg.epochs)
        return w, hist, eta

    shm = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,     # the SVRG carry w becomes data-varying inside
    )

    def traced(w0, xs, ys, wts):
        _EPOCH_TRACE_COUNTER.bump(("sharded", cfg, M))
        return shm(w0, xs, ys, wts)

    return jax.jit(traced)


def make_sharded_epoch(mesh: jax.sharding.Mesh, params: ODMParams,
                       cfg: DSVRGConfig, M: int, data_axis: str = "data",
                       eta: float | None = None):
    """Builds a jit'd SPMD *single*-epoch function over partitions sharded
    on ``data_axis``: (w, xs, ys) -> (w', obj_global). Validation helper —
    production solves go through the epoch-scan driver (_solve_sharded),
    which never hands w back to host between epochs.

    When ``eta`` is omitted and ``cfg.eta <= 0`` the step size is the
    ``auto_eta`` smoothness step computed from the sharded data (psum of
    the local ‖x‖² sums) — identical to the single-process step size.
    """
    fused = _resolve_fused(cfg)

    def epoch(w, xs, ys):
        # xs: (K_loc, m, d) local slab on each device
        xsb, ysb, wts = _pad_batches(xs, ys, cfg.batch)
        eta_v = _sharded_eta(xsb, ysb, wts, params, cfg, M, data_axis, eta)
        return _sharded_epoch(w, xsb, ysb, wts, eta_v, params, cfg, M,
                              data_axis, fused)

    return jax.jit(jax.shard_map(
        epoch, mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis)),
        out_specs=(P(), P()),
        check_vma=False,     # the SVRG carry w becomes data-varying inside
    ))


def _solve_sharded(x: Array, y: Array, params: ODMParams, cfg: DSVRGConfig,
                   key: jax.Array, mesh: jax.sharding.Mesh,
                   data_axis: str = "data",
                   w0: Array | None = None, *, faults=None, tracker=None,
                   resume=None) -> DSVRGResult:
    M, d = x.shape
    K = cfg.n_partitions
    n_dev = mesh.shape[data_axis]
    if M % K != 0:
        raise ValueError(f"K={K} must divide M={M}")
    if K % n_dev != 0:
        raise ValueError(f"K={K} must be a multiple of data axis size {n_dev}")
    if cfg.schedule not in ("serial", "parallel"):
        raise ValueError(f"unknown schedule {cfg.schedule!r}")

    perm = _partition_perm(x, cfg, K, key)
    xp, yp = x[perm], y[perm]
    xs, ys, wts = _pad_batches(xp.reshape(K, M // K, d),
                               yp.reshape(K, M // K), cfg.batch)

    w0 = jnp.zeros(d, x.dtype) if w0 is None else w0
    if faults is None and tracker is None and resume is None:
        run = _make_sharded_run(mesh, params, cfg, M, data_axis)
        w, hist, eta = run(w0, xs, ys, wts)
    else:
        def runner(w, n):
            run = _make_sharded_run(mesh, params,
                                    dataclasses.replace(cfg, epochs=n),
                                    M, data_axis)
            return run(w, xs, ys, wts)

        w, hist, eta = _segmented(runner, w0, cfg, M, perm=perm,
                                  faults=faults, tracker=tracker,
                                  resume=resume)
    return DSVRGResult(w=w, history=hist, perm=perm, eta=eta)
