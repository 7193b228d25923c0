"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program where the timed path runs it (the
harness's look for a chip is skipped); the rest of the run is the
harness's own. The cells run on one chip, so no fault leaves out an
exchange between chips.
"""
import jax
import jax.numpy as jnp
import pytest

import tiny


@pytest.fixture(autouse=True)
def _forget_planted_programs():
    """A planted fault is traced into jitted programs the process keeps
    (the streaming steps are cached per configuration); drop them after
    each test, so the next test in this process runs the real program."""
    yield
    from repro.core import dsvrg
    dsvrg._make_stream_steps.cache_clear()
    jax.clear_caches()


def _fit_step_unchanged(mp):
    from repro.core import engines

    def solve(xs, ys, alphas, **kw):
        K = xs.shape[0]
        return alphas, jnp.zeros((K,), jnp.int32), jnp.zeros((K,))
    mp.setattr(engines, "solve_level_pallas", solve)


def _fit_half_batch(mp):
    from repro.core import engines
    real = engines.solve_level_pallas

    def solve(xs, ys, alphas, **kw):
        a, s, k = real(xs, ys, alphas, **kw)
        m = xs.shape[1]
        keep = (jnp.arange(m) < m // 2).astype(a.dtype)
        return a * jnp.concatenate([keep, keep])[None, :], s, k
    mp.setattr(engines, "solve_level_pallas", solve)


def _scores_altered(mp):
    from repro.kernels import ops
    real = ops.decision_scores

    def scores(x, *a, **kw):
        return real(x, *a, **kw).at[0].add(1e-2)
    mp.setattr(ops, "decision_scores", scores)


def _stream_step_unchanged(mp):
    from repro.core import dsvrg
    mp.setattr(dsvrg, "_direction", lambda w, *a, **k: jnp.zeros_like(w))


def _stream_half_batch(mp):
    from repro.core import dsvrg
    real = dsvrg._direction

    def half(w, anchor, h, xb, yb, wb, params, fused):
        n = xb.shape[0] // 2
        return real(w, anchor, h, xb[:n], yb[:n], wb[:n], params, fused)
    mp.setattr(dsvrg, "_direction", half)


def _stream_w_altered(mp):
    from repro.core import dsvrg
    real = dsvrg._solve_stream

    def solve(*a, **k):
        res, kkt = real(*a, **k)
        return res._replace(w=res.w.at[0].add(1e-2 * jnp.max(
            jnp.abs(res.w)))), kkt
    mp.setattr(dsvrg, "_solve_stream", solve)


FAULTS = [
    ("cod-rna-rbf.fit", _fit_step_unchanged, "kkt"),
    ("cod-rna-rbf.fit", _fit_half_batch, "kkt"),
    ("cod-rna-rbf.fit", _scores_altered, "f_gap"),
    ("susy-linear.fit", _stream_step_unchanged, "w_gap"),
    ("susy-linear.fit", _stream_half_batch, "w_gap"),
    ("susy-linear.fit", _stream_w_altered, "w_gap"),
]


@pytest.mark.parametrize("name, plant, caught", FAULTS,
                         ids=[f"{n}-{f.__name__[1:]}" for n, f, _ in FAULTS])
def test_fault_is_not_correct(name, plant, caught, monkeypatch):
    plant(monkeypatch)
    out = tiny.run(name)
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"]
