"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(tests/test_kernels.py sweeps shapes & dtypes with assert_allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.precision import matmul

Array = jax.Array


# ---------------------------------------------------------------------------
# rbf_gram
# ---------------------------------------------------------------------------

def rbf_gram(x: Array, z: Array, gamma: float) -> Array:
    """K[i,j] = exp(-gamma ||x_i - z_j||^2)."""
    xx = jnp.sum(x * x, axis=-1)[:, None]
    zz = jnp.sum(z * z, axis=-1)[None, :]
    cross = matmul(x, z.T)
    return jnp.exp(-gamma * jnp.maximum(xx + zz - 2.0 * cross, 0.0))


def signed_rbf_gram(x: Array, z: Array, yx: Array, yz: Array,
                    gamma: float) -> Array:
    """Q[i,j] = y_i y_j exp(-gamma ||x_i - z_j||^2) — the ODM dual block."""
    return (yx[:, None] * yz[None, :]) * rbf_gram(x, z, gamma)


# ---------------------------------------------------------------------------
# dual_cd_block — Gauss-Southwell (greedy) CD within a VMEM-resident tile
# ---------------------------------------------------------------------------

def cd_tile_sweep(qblk: Array, alpha: Array, u: Array, *, c: float,
                  ups: float, theta: float, mscale: float,
                  n_steps: int) -> tuple[Array, Array]:
    """Greedy coordinate descent on one diagonal tile.

    qblk:  (B, B) diagonal Gram block (signed).
    alpha: (2B,) [zeta; beta] for the tile's coordinates.
    u:     (B,) cache Q(zeta - beta) restricted to the tile's rows
           (external contribution included; it stays constant here).

    Each step picks the coordinate with the largest projected-gradient
    violation (Gauss-Southwell rule) and applies the exact univariate
    update. All ops are vectorized (argmax + one-hot) — the TPU-friendly
    formulation the Pallas kernel mirrors exactly.
    """
    B = qblk.shape[0]
    q_diag = jnp.diagonal(qblk)

    def step(carry, _):
        alpha, u = carry
        zeta, beta = alpha[:B], alpha[B:]
        gz = u + mscale * c * ups * zeta + (theta - 1.0)
        gb = -u + mscale * c * beta + (theta + 1.0)
        g = jnp.concatenate([gz, gb])
        # projected violation for the box alpha >= 0
        viol = jnp.where(alpha > 0.0, jnp.abs(g), jnp.maximum(-g, 0.0))
        i = jnp.argmax(viol)
        hz = q_diag + mscale * c * ups
        hb = q_diag + mscale * c
        h = jnp.concatenate([hz, hb])
        new_i = jnp.maximum(alpha[i] - g[i] / h[i], 0.0)
        delta = new_i - alpha[i]
        alpha = alpha.at[i].set(new_i)
        row = jnp.where(i < B, i, i - B)
        sign = jnp.where(i < B, 1.0, -1.0).astype(u.dtype)
        onehot = (jnp.arange(B) == row).astype(u.dtype)
        u = u + (sign * delta) * matmul(qblk, onehot)
        return (alpha, u), None

    (alpha, u), _ = jax.lax.scan(step, (alpha, u), None, length=n_steps)
    return alpha, u


def cd_block_sweep(q_blocks: Array, alphas: Array, us: Array, *, c: float,
                   ups: float, theta: float, mscale: float,
                   n_steps: int) -> tuple[Array, Array]:
    """vmap of cd_tile_sweep over the leading tile axis.

    q_blocks (nblk, B, B), alphas (nblk, 2B), us (nblk, B).
    """
    fn = lambda q, a, u: cd_tile_sweep(q, a, u, c=c, ups=ups, theta=theta,
                                       mscale=mscale, n_steps=n_steps)
    return jax.vmap(fn)(q_blocks, alphas, us)


# ---------------------------------------------------------------------------
# odm_grad — fused linear-kernel primal ODM gradient
# ---------------------------------------------------------------------------

def odm_grad(w: Array, x: Array, y: Array, *, lam: float, theta: float,
             ups: float) -> Array:
    """grad p(w) = w + (lam / (M (1-theta)^2)) X^T [(lo + ups*hi) * y]."""
    M = x.shape[0]
    m = y * matmul(x, w)
    s = lam / (M * (1.0 - theta) ** 2)
    lo = jnp.where(m < 1.0 - theta, m + theta - 1.0, 0.0)
    hi = jnp.where(m > 1.0 + theta, m - theta - 1.0, 0.0)
    coef = s * (lo + ups * hi) * y
    return w + matmul(x.T, coef)


# ---------------------------------------------------------------------------
# flash attention (causal, optional sliding window, GQA)
# ---------------------------------------------------------------------------

def mha(q: Array, k: Array, v: Array, *, causal: bool = True,
        window: int | None = None, scale: float | None = None) -> Array:
    """Reference attention. q (B, Hq, T, D), k/v (B, Hkv, S, D).

    GQA: Hq % Hkv == 0; query head h attends to kv head h // (Hq // Hkv).
    window: if set, query position t attends only to kv in
    (t - window, t] (causal sliding window, Gemma/recurrentgemma style).
    """
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhtd,bhsd->bhts", q, kq) * scale
    # positions: queries occupy the last T slots of the S-long history
    qpos = jnp.arange(T) + (S - T)
    kpos = jnp.arange(S)
    mask = jnp.ones((T, S), bool)
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows give nan; zero them (cannot happen for causal+window>=1)
    probs = jnp.nan_to_num(probs)
    return jnp.einsum("bhts,bhsd->bhtd", probs, vq)
