"""Optimal margin Distribution Machine (ODM) — primal and dual forms.

Paper: Zhang & Zhou 2019 (ODM); Wang et al. IJCAI 2023 (SODM) Eqns. (1)-(3).

Primal (Eqn. 9 of the appendix):

    min_w  p(w) = 1/2 ||w||^2 + lam/(2 M (1-theta)^2) * sum_i (xi_i^2 + ups*eps_i^2)
    s.t.   1 - theta - xi_i <= y_i w^T phi(x_i) <= 1 + theta + eps_i

Dual (Eqn. 1/2), alpha = [zeta; beta] in R^{2M}_+:

    min_alpha f(alpha) = 1/2 alpha^T H alpha + b^T alpha
    H = [[Q + M c ups I, -Q], [-Q, Q + M c I]]
    b = [(theta-1) 1_M ; (theta+1) 1_M],   c = (1-theta)^2 / (lam ups)

Strong duality holds with p(w*) = -f(alpha*).

Everything here is pure jnp so it can run inside jit / shard_map / scan.
The *scale* of the regularizer (the "M" multiplying c) is an explicit
argument ``mscale`` because SODM's local subproblems use m = M/K in that
slot (Eqn. 4) while keeping the same c.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kernel_fns as kf
from repro.precision import matmul

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ODMParams:
    """Hyperparameters of ODM. ``ups`` is the paper's upsilon (v)."""

    lam: float = 1.0
    theta: float = 0.1
    ups: float = 0.5

    @property
    def c(self) -> float:
        """c = (1-theta)^2 / (lam * ups), constant in the dual Hessian."""
        return (1.0 - self.theta) ** 2 / (self.lam * self.ups)


class DualState(NamedTuple):
    """State threaded through dual coordinate descent.

    alpha:  (2m,) dual variables [zeta; beta] >= 0.
    u:      (m,) maintained product Q @ (zeta - beta)  (gradient cache).
    """

    alpha: Array
    u: Array


# ---------------------------------------------------------------------------
# dual form
# ---------------------------------------------------------------------------

def split_alpha(alpha: Array) -> tuple[Array, Array]:
    m = alpha.shape[0] // 2
    return alpha[:m], alpha[m:]


def dual_objective(Q: Array, alpha: Array, params: ODMParams,
                   mscale: float) -> Array:
    """f(alpha) = 1/2 a^T H a + b^T a with explicit regularizer scale."""
    zeta, beta = split_alpha(alpha)
    gam = zeta - beta
    quad = 0.5 * matmul(gam, matmul(Q, gam))
    reg = 0.5 * mscale * params.c * (params.ups * matmul(zeta, zeta)
                                     + matmul(beta, beta))
    lin = (params.theta - 1.0) * jnp.sum(zeta) + (params.theta + 1.0) * jnp.sum(beta)
    return quad + reg + lin


def dual_grad(Q: Array, alpha: Array, params: ODMParams,
              mscale: float) -> Array:
    """grad f(alpha) = H alpha + b, computed via u = Q (zeta-beta)."""
    zeta, beta = split_alpha(alpha)
    u = matmul(Q, zeta - beta)
    return dual_grad_from_u(u, alpha, params, mscale)


def dual_grad_from_u(u: Array, alpha: Array, params: ODMParams,
                     mscale: float) -> Array:
    """Gradient given the cached u = Q (zeta - beta)."""
    zeta, beta = split_alpha(alpha)
    gz = u + mscale * params.c * params.ups * zeta + (params.theta - 1.0)
    gb = -u + mscale * params.c * beta + (params.theta + 1.0)
    return jnp.concatenate([gz, gb])


def warm_start_scale(u: Array, alpha: Array, params: ODMParams,
                     mscale: float) -> Array:
    """Optimal scalar t for a warm start: argmin_t f(t · alpha).

    f is quadratic along the ray, f(t·a) = t²·(½ aᵀH a) + t·(bᵀa), so
    t* = -bᵀa / (aᵀH a), clipped to t ≥ 0 (box constraint). SODM merges
    concatenate child duals solved at regularizer scale m into a parent
    solve at scale p·m; the right correction is ≈1/p when the m·c·I term
    dominates H and ≈1 when Q dominates — this line search lands on the
    optimum in either regime for one cached matvec (``u = Q (zeta-beta)``,
    which the solvers need anyway). t = 1 for a zero (cold) start.
    """
    zeta, beta = split_alpha(alpha)
    gam = zeta - beta
    quad = matmul(gam, u) + mscale * params.c * (
        params.ups * matmul(zeta, zeta) + matmul(beta, beta))
    lin = (params.theta - 1.0) * jnp.sum(zeta) \
        + (params.theta + 1.0) * jnp.sum(beta)
    return jnp.where(quad > 0.0, jnp.maximum(-lin / quad, 0.0), 1.0)


def hess_diag(q_diag: Array, params: ODMParams, mscale: float) -> Array:
    """diag(H) = [Q_ii + M c ups; Q_ii + M c]."""
    hz = q_diag + mscale * params.c * params.ups
    hb = q_diag + mscale * params.c
    return jnp.concatenate([hz, hb])


def kkt_residual(Q: Array, alpha: Array, params: ODMParams,
                 mscale: float) -> Array:
    """Projected-gradient infinity norm for the box constraint alpha >= 0.

    At optimum: grad_i >= 0 where alpha_i = 0, grad_i = 0 where alpha_i > 0.
    """
    g = dual_grad(Q, alpha, params, mscale)
    proj = jnp.where(alpha > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
    return jnp.max(proj)


# ---------------------------------------------------------------------------
# primal form (linear kernel)
# ---------------------------------------------------------------------------

def margins(w: Array, x: Array, y: Array) -> Array:
    """y_i w^T x_i, shape (M,)."""
    return y * matmul(x, w)


def _hinge_coef(m: Array, y: Array, params: ODMParams) -> Array:
    """Per-instance quadratic-hinge coefficient s·(lo + ups·hi)·y.

    s = lam/(1-theta)² is the per-instance scale (no 1/M); every gradient
    form below divides by its own instance count. Rows with y = 0
    (padding) get coefficient exactly 0.
    """
    s = params.lam / (1.0 - params.theta) ** 2
    lo = jnp.where(m < 1.0 - params.theta, m + params.theta - 1.0, 0.0)
    hi = jnp.where(m > 1.0 + params.theta, m - params.theta - 1.0, 0.0)
    return s * (lo + params.ups * hi) * y


def primal_objective(w: Array, x: Array, y: Array, params: ODMParams,
                     weights: Array | None = None,
                     total: int | None = None) -> Array:
    """p(w). ``weights`` masks rows (padded rows get 0); ``total`` is the
    true instance count when ``x`` carries padding rows — the loss is
    normalized by it, so a sharded caller can recover the global objective
    as ``ridge + psum(local - ridge)`` with every shard passing the global
    M as ``total``."""
    m = margins(w, x, y)
    xi = jnp.maximum(0.0, (1.0 - params.theta) - m)
    eps = jnp.maximum(0.0, m - (1.0 + params.theta))
    terms = xi * xi + params.ups * (eps * eps)
    if weights is not None:
        terms = weights * terms
    M = x.shape[0] if total is None else total
    loss = jnp.sum(terms) * params.lam / (2.0 * M * (1.0 - params.theta) ** 2)
    return 0.5 * matmul(w, w) + loss


def primal_grad(w: Array, x: Array, y: Array, params: ODMParams,
                total: int | None = None) -> Array:
    """Full-batch grad p(w); matches the mean of per-instance grads below.

    ``total`` is the true instance count when ``x`` carries padding rows —
    padded rows must have y = 0 (their coefficient is then exactly 0).
    """
    M = x.shape[0] if total is None else total
    coef = _hinge_coef(margins(w, x, y), y, params)      # (M,)
    return w + matmul(x.T, coef) / M


def per_instance_grad(w: Array, x_i: Array, y_i: Array, params: ODMParams,
                      M: int) -> Array:
    """The paper's nabla p_i(w) (Section 3.3) — unbiased: E_i[...] = grad p.

    The paper's per-instance loss term carries no 1/M (it is M times the
    instance's 1/M share of the empirical loss), so a uniformly sampled i
    gives an unbiased estimator of the full gradient. ``M`` is accepted for
    signature parity with :func:`minibatch_grad` but unused.
    """
    del M
    m = y_i * matmul(x_i, w)
    return w + _hinge_coef(m, y_i, params) * x_i


def minibatch_grad(w: Array, xb: Array, yb: Array, params: ODMParams,
                   M: int) -> Array:
    """Mean over the batch of the paper's per-instance gradients.

    E_batch[minibatch_grad] = primal_grad when instances are drawn uniformly,
    because each per-instance grad is w + M * (its 1/M loss-grad share).
    ``M`` is accepted for signature parity but unused.
    """
    del M
    coef = _hinge_coef(yb * matmul(xb, w), yb, params)  # (B,)
    # mean_i [ w + coef_i x_i ] = w + (1/B) X^T coef
    return w + matmul(xb.T, coef) / xb.shape[0]


def svrg_direction(w: Array, anchor: Array, h: Array, xb: Array, yb: Array,
                   params: ODMParams, wb: Array | None = None) -> Array:
    """DSVRG inner-step direction  g_w − g_a + h  on one minibatch.

    Expanding both :func:`minibatch_grad` terms, the ridge parts cancel to
    ``w − anchor`` and the hinge parts share the same X, so the direction is

        (w − anchor + h) + Xᵀ(coef_w − coef_a) / n_valid

    — one pass over the batch instead of two independent gradients. ``wb``
    masks ragged-tail padding rows (0 ⇒ excluded from both the coefficient
    and the mean divisor); omitted means all rows count. This is the pure
    jnp reference of the fused Pallas kernel
    (:func:`repro.kernels.ops.svrg_grad`).
    """
    mm = yb[:, None] * matmul(xb, jnp.stack([w, anchor], axis=1))  # (B, 2)
    dcoef = _hinge_coef(mm[:, 0], yb, params) \
        - _hinge_coef(mm[:, 1], yb, params)
    if wb is None:
        n = xb.shape[0]
    else:
        dcoef = wb * dcoef
        n = jnp.maximum(jnp.sum(wb), 1.0)
    return (w - anchor + h) + matmul(xb.T, dcoef) / n


# ---------------------------------------------------------------------------
# primal <-> dual bridges and prediction
# ---------------------------------------------------------------------------

def w_from_alpha(x: Array, y: Array, alpha: Array) -> Array:
    """KKT: w = X Y (zeta - beta) — linear kernel only."""
    zeta, beta = split_alpha(alpha)
    return matmul(x.T, y * (zeta - beta))


def alpha_from_w(w: Array, x: Array, y: Array, params: ODMParams) -> Array:
    """Inverse KKT map: dual [zeta; beta] from a primal solution w.

    At a primal stationary point the complementary-slackness conditions
    give zeta_i = s·xi_i and beta_i = s·ups·eps_i with
    s = lam/(M(1-theta)²) — substituting back,
    w = Xᵀ(y ⊙ (zeta − beta)) recovers w exactly. Used by the DSVRG
    solver engine so a primal linear solve plugs into every dual-alpha
    consumer (predict / dual_objective / SODMResult). Exact only at
    stationarity; mid-optimization it is the dual of the *projected*
    primal point.
    """
    m = margins(w, x, y)
    xi = jnp.maximum(0.0, (1.0 - params.theta) - m)
    eps = jnp.maximum(0.0, m - (1.0 + params.theta))
    s = params.lam / (x.shape[0] * (1.0 - params.theta) ** 2)
    return jnp.concatenate([s * xi, s * params.ups * eps])


def decision_function(spec: kf.KernelSpec, x_train: Array, y_train: Array,
                      alpha: Array, x_test: Array) -> Array:
    """f(x) = sum_i y_i (zeta_i - beta_i) kappa(x_i, x).

    Dense oracle: materializes the full (T, M) test Gram. Kept as the
    exact-expansion reference the serving subsystem is validated against;
    production scoring goes through :func:`predict` / ``repro.serve``
    (compiled artifact + tiled matrix-free scorer, no (T, M) block).
    """
    zeta, beta = split_alpha(alpha)
    coef = y_train * (zeta - beta)
    return matmul(kf.gram(spec, x_test, x_train), coef)


def predict(spec: kf.KernelSpec, x_train: Array, y_train: Array,
            alpha: Array, x_test: Array) -> Array:
    """Served prediction: compiles the dual into a ``FittedODM`` (exact-
    zero coefficients pruned, linear kernels collapsed to w) and scores
    through the tiled matrix-free kernel — O(T·B) memory instead of the
    dense (T, M) Gram of :func:`decision_function`. Host-side API (the
    compile step gathers); call ``FittedODM.predict`` directly inside jit.
    """
    from repro.serve import model as serve_model   # deferred: serving layer
    m = serve_model.compile_model(spec, x_train, y_train, alpha)
    return m.predict(x_test)


def accuracy(y_true: Array, y_pred: Array) -> Array:
    return jnp.mean((y_true * y_pred) > 0.0)
