"""The plain reference of what the cells compute. It imports nothing of
the program (``repro``) and takes nothing the program made except the
answer it judges.

ODM (Zhang & Zhou 2019; Wang et al., IJCAI 2023, Eqns. 1-3). For the dual
``alpha = [zeta; beta] >= 0`` of ``M`` rows with labels ``y`` and kernel
``k``, ``Q = Y K Y``, ``c = (1 - theta)^2 / (lam ups)`` and the gradient

    g_zeta = u + M c ups zeta + (theta - 1),
    g_beta = -u + M c beta + (theta + 1),     u = Q (zeta - beta),

the KKT residual is the largest projected gradient: ``|g_i|`` where
``alpha_i > 0`` and ``max(-g_i, 0)`` where ``alpha_i = 0``. The decision
function is ``f(x) = sum_i y_i (zeta_i - beta_i) k(x_i, x)``.

The rbf kernel is evaluated from differences, ``exp(-gamma sum_k
(x_k - z_k)^2)``, elementwise in float32 with no matrix product, so the
reference does not depend on the chip's matrix-unit precision. Products
run in blocks of rows so that they fit beside nothing else.

The linear primal (Algorithm 2, DSVRG) is replayed on the host in
float64, in the order the streamed fit consumes its rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("gamma",))
def _rbf_block(xq, z, coef, *, gamma):
    diff = xq[:, None, :] - z[None, :, :]
    k = jnp.exp(-gamma * jnp.sum(diff * diff, axis=-1))
    return jnp.sum(k * coef[None, :], axis=1)


def rbf_expand(xq, z, coef, gamma: float, block: int = 256) -> np.ndarray:
    """``sum_j coef_j exp(-gamma |xq_i - z_j|^2)`` for every row of
    ``xq``, in blocks of ``block`` query rows (host float64 result)."""
    xq = jnp.asarray(xq, jnp.float32)
    z = jnp.asarray(z, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    n = xq.shape[0]
    pad = -n % block
    xp = jnp.pad(xq, ((0, pad), (0, 0)))
    out = [np.asarray(_rbf_block(xp[i:i + block], z, coef, gamma=gamma),
                      np.float64) for i in range(0, n + pad, block)]
    return np.concatenate(out)[:n]


def decision(x_train, y_train, alpha, x_query, gamma: float) -> np.ndarray:
    """f(x_query) of the dual ``alpha`` over the training rows."""
    M = x_train.shape[0]
    a = np.asarray(alpha, np.float64)
    coef = np.asarray(y_train, np.float64) * (a[:M] - a[M:])
    return rbf_expand(x_query, x_train, coef, gamma)


def kkt_residual(x, y, alpha, gamma: float, lam: float, theta: float,
                 ups: float) -> float:
    """The full problem's KKT residual of ``alpha`` (rbf kernel)."""
    M = x.shape[0]
    a = np.asarray(alpha, np.float64)
    zeta, beta = a[:M], a[M:]
    yv = np.asarray(y, np.float64)
    # u = Y K Y (zeta - beta), K symmetric: one expansion over the rows
    u = yv * rbf_expand(x, x, yv * (zeta - beta), gamma)
    c = (1.0 - theta) ** 2 / (lam * ups)
    gz = u + M * c * ups * zeta + (theta - 1.0)
    gb = -u + M * c * beta + (theta + 1.0)
    g = np.concatenate([gz, gb])
    proj = np.where(a > 0.0, np.abs(g), np.maximum(-g, 0.0))
    return float(np.max(proj))


# ---------------------------------------------------------------------------
# Algorithm 2: the streamed serial DSVRG chain, float64 on the host
# ---------------------------------------------------------------------------

def _hinge(mrg, y, lam, theta, ups):
    """Per-row coefficient of the quadratic hinge's gradient, y times
    s (lo + ups hi) with s = lam / (1 - theta)^2."""
    s = lam / (1.0 - theta) ** 2
    lo = np.where(mrg < 1.0 - theta, mrg + theta - 1.0, 0.0)
    hi = np.where(mrg > 1.0 + theta, mrg - theta - 1.0, 0.0)
    return s * (lo + ups * hi) * y


def dsvrg_stream(read_rows, M: int, d: int, *, lam: float, theta: float,
                 ups: float, epochs: int, batch: int,
                 slab: int = 1 << 16) -> tuple[np.ndarray, float]:
    """(w, eta) of the serial DSVRG chain over rows in stream order.

    ``read_rows(lo, hi)`` returns rows ``[lo, hi)`` as ``(x, y)``. Per
    epoch: the anchor is the current ``w``; the full gradient ``h = a +
    (1/M) X^T coef(a)``; then one step per minibatch of ``batch``
    consecutive rows, ``w -= eta ((w - a + h) + X_b^T (coef_b(w) -
    coef_b(a)) / n_b)``, the last minibatch holding the ragged tail. The
    step is ``eta = 0.5 / (1 + s sum|x|^2 / M)``.
    """
    s = lam / (1.0 - theta) ** 2

    def full_grad(a):
        g = np.zeros(d)
        for lo in range(0, M, slab):
            x, y = read_rows(lo, min(lo + slab, M))
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            g += x.T @ _hinge(y * (x @ a), y, lam, theta, ups)
        return a + g / M

    sumsq = 0.0
    for lo in range(0, M, slab):
        x, _ = read_rows(lo, min(lo + slab, M))
        sumsq += float(np.sum(np.asarray(x, np.float64) ** 2))
    eta = 0.5 / (1.0 + s * sumsq / M)

    w = np.zeros(d)
    per = max(batch, slab - slab % batch)     # whole minibatches per read
    for _ in range(epochs):
        a = w.copy()
        h = full_grad(a)
        for lo in range(0, M, per):
            x, y = read_rows(lo, min(lo + per, M))
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            for b0 in range(0, x.shape[0], batch):
                xb, yb = x[b0:b0 + batch], y[b0:b0 + batch]
                wa = xb @ np.stack([w, a], axis=1)
                dc = (_hinge(yb * wa[:, 0], yb, lam, theta, ups)
                      - _hinge(yb * wa[:, 1], yb, lam, theta, ups))
                w = w - eta * ((w - a + h) + xb.T @ dc / xb.shape[0])
    return w, eta
