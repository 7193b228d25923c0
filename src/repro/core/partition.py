"""SODM distribution-aware partition strategy (paper Section 3.2).

Three steps, all jit-safe:

1. **Landmark selection** (Eqn. 8): z_1 = x_1; then greedily
   z_{s+1} = argmin_z  K_{s,z}^T K_{s,s}^{-1} K_{s,z}
   over the data set, which maximizes the Gram determinant of the landmark
   set (Schur complement) and hence the minimal principal angle tau between
   strata. We solve the argmin exactly over all candidates each round —
   O(S * M * s^2) with tiny s, the "computationally efficient" claim of the
   paper — using a Cholesky of K_ss that is updated incrementally.

2. **Stratum assignment** (Eqn. 7): phi(i) = argmin_s ||phi(x_i) - phi(z_s)||
   = argmax_s kappa(x_i, z_s) for shift-invariant kernels (||phi|| = r const),
   and we use the general form -2k(x,z)+k(z,z) otherwise.

3. **Stratified partitioning**: each stratum is split into K equal pieces by
   random sampling without replacement; partition k takes piece k of every
   stratum, so every partition preserves the global distribution.

The output is a permutation ``perm`` of [M] such that instances
perm[k*m:(k+1)*m] form partition k — downstream code (sodm.py) applies the
permutation once and then works on contiguous slabs, which is exactly the
layout shard_map wants.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kernel_fns as kf
from repro.precision import matmul

Array = jax.Array


class PartitionPlan(NamedTuple):
    perm: Array          # (M,) permutation: partition k = perm[k*m:(k+1)*m]
    landmarks: Array     # (S,) indices of the landmark points
    stratum: Array       # (M,) stratum index of each ORIGINAL instance
    n_partitions: int    # static K


# ---------------------------------------------------------------------------
# landmark selection (Eqn. 8)
# ---------------------------------------------------------------------------

def select_landmarks(spec: kf.KernelSpec, x: Array, n_landmarks: int,
                     jitter: float = 1e-6) -> Array:
    """Greedy determinant-maximizing landmark indices (Eqn. 8).

    Equivalent to greedy MAP inference of a DPP / pivoted-Cholesky column
    selection: the Schur complement r^2 - K_sz^T K_ss^-1 K_sz is exactly the
    *residual diagonal* of the pivoted Cholesky, so we select the argmax
    residual each round and update the residual in O(M) — total O(S M d)
    for the kernel columns plus O(S^2 M) updates.
    """
    M = x.shape[0]
    diag = kf.gram_diag(spec, x)                       # (M,) kappa(x_i, x_i)
    # residual diagonal of the pivoted Cholesky of the full Gram
    resid = diag
    # L factors against chosen pivots: rows (s, M) built incrementally
    L = jnp.zeros((n_landmarks, M), x.dtype)
    picks = jnp.zeros((n_landmarks,), jnp.int32)

    def body(s, carry):
        resid, L, picks = carry
        # paper: z_1 = x_1 ("any choice makes no difference"); then greedy.
        i = jnp.where(s == 0, 0, jnp.argmax(resid))
        picks = picks.at[s].set(i)
        kcol = kf.gram(spec, x, jax.lax.dynamic_slice(x, (i, 0), (1, x.shape[1])))[:, 0]
        # ell = (k(:, i) - L[:s].T @ L[:s, i]) / sqrt(resid[i])
        proj = matmul(L.T, L[:, i])                           # (M,) uses only rows < s (others are 0)
        denom = jnp.sqrt(jnp.maximum(resid[i], jitter))
        ell = (kcol - proj) / denom
        L = L.at[s].set(ell)
        resid = jnp.maximum(resid - ell * ell, 0.0)
        # never re-pick: zero the residual at i
        resid = resid.at[i].set(0.0)
        return resid, L, picks

    _, _, picks = jax.lax.fori_loop(0, n_landmarks, body, (resid, L, picks))
    return picks


# ---------------------------------------------------------------------------
# stratum assignment (Eqn. 7)
# ---------------------------------------------------------------------------

def assign_strata(spec: kf.KernelSpec, x: Array, landmark_idx: Array) -> Array:
    """phi(i) = argmin_s ||phi(x_i) - phi(z_s)||^2 in the RKHS.

    ||phi(x)-phi(z)||^2 = k(x,x) - 2 k(x,z) + k(z,z); k(x,x) is constant in
    s so the argmin needs only the last two terms.
    """
    z = x[landmark_idx]                                 # (S, d)
    kxz = kf.gram(spec, x, z)                           # (M, S)
    kzz = kf.gram_diag(spec, z)                         # (S,)
    d2 = kzz[None, :] - 2.0 * kxz
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# stratified partition construction
# ---------------------------------------------------------------------------

def stratified_partitions(stratum: Array, n_partitions: int,
                          key: jax.Array) -> Array:
    """Permutation placing a proportional random slice of every stratum in
    each partition.

    Implementation trick (fully vectorized, no ragged loops): sort instances
    by (stratum, random tiebreak); within each stratum the order is uniform;
    then assign instance ranked r *within its stratum* to partition
    r mod K — a perfect round-robin deal that splits every stratum into K
    near-equal pieces. Finally sort by (partition, random) to produce the
    contiguous-slab permutation. Partition sizes differ by at most S when
    stratum sizes are not multiples of K; we rebalance to exactly M/K by a
    final round-robin of the overflow, preserving per-stratum proportions
    up to +-1.
    """
    M = stratum.shape[0]
    K = n_partitions
    k1, k2 = jax.random.split(key)
    tie = jax.random.uniform(k1, (M,))
    # rank of each instance within its stratum
    order = jnp.lexsort((tie, stratum))                 # sorted by stratum then tie
    # position within stratum: index along sorted order minus start of stratum
    sorted_stratum = stratum[order]
    is_start = jnp.concatenate([jnp.ones(1, jnp.int32),
                                (sorted_stratum[1:] != sorted_stratum[:-1]).astype(jnp.int32)])
    seg_id = jnp.cumsum(is_start) - 1                   # dense stratum id along order
    pos_global = jnp.arange(M)
    seg_start = jnp.zeros(M, jnp.int32).at[seg_id].max(
        jnp.where(is_start == 1, pos_global, 0).astype(jnp.int32))
    # within-stratum rank
    rank = pos_global - seg_start[seg_id]
    part_of_sorted = (rank % K).astype(jnp.int32)
    # scatter back to original order
    part = jnp.zeros(M, jnp.int32).at[order].set(part_of_sorted)

    # rebalance to exact size m = M // K (assumes K | M, enforced by caller):
    # sort by (partition, random); oversized partitions' tail spills into
    # undersized ones by re-assigning global rank r -> r // m.
    tie2 = jax.random.uniform(k2, (M,))
    order2 = jnp.lexsort((tie2, part))
    m = M // K
    final_part_sorted = (jnp.arange(M) // m).astype(jnp.int32)
    del final_part_sorted  # implicit: position r in order2 goes to partition r//m
    return order2


def make_plan(spec: kf.KernelSpec, x: Array, n_landmarks: int,
              n_partitions: int, key: jax.Array) -> PartitionPlan:
    """Full Section-3.2 pipeline: landmarks -> strata -> partitions."""
    M = x.shape[0]
    if M % n_partitions != 0:
        raise ValueError(f"K={n_partitions} must divide M={M} "
                         "(pad or trim the data set first)")
    landmarks = select_landmarks(spec, x, n_landmarks)
    stratum = assign_strata(spec, x, landmarks)
    perm = stratified_partitions(stratum, n_partitions, key)
    return PartitionPlan(perm=perm, landmarks=landmarks, stratum=stratum,
                         n_partitions=n_partitions)


# ---------------------------------------------------------------------------
# rival partition strategies (for ablation / baselines)
# ---------------------------------------------------------------------------

def random_partitions(M: int, n_partitions: int, key: jax.Array) -> Array:
    """Uniform random permutation — the strawman SODM improves on."""
    return jax.random.permutation(key, M)


def cluster_partitions(spec: kf.KernelSpec, x: Array, n_partitions: int,
                       key: jax.Array, iters: int = 10) -> Array:
    """Kernel k-means-style clusters-as-partitions (DC-SVM / DiP-SVM style).

    Lloyd's algorithm in input space (the common practical surrogate), then
    *clusters become partitions*: sort by cluster and deal contiguous slabs.
    Cluster sizes are forced to M/K by ranking within cluster and spilling
    the tail round-robin (same rebalance trick as above) so downstream code
    sees equal slabs; this mirrors how DC-SVM pads/limits cluster sizes.
    """
    M, d = x.shape
    K = n_partitions
    init = jax.random.choice(key, M, (K,), replace=False)
    cent = x[init]

    def step(cent, _):
        d2 = (jnp.sum(x * x, 1)[:, None] + jnp.sum(cent * cent, 1)[None, :]
              - 2.0 * matmul(x, cent.T))
        a = jnp.argmin(d2, 1)
        onehot = jax.nn.one_hot(a, K, dtype=x.dtype)
        counts = jnp.maximum(onehot.sum(0), 1.0)
        cent = matmul(onehot.T, x) / counts[:, None]
        return cent, a

    cent, assigns = jax.lax.scan(step, cent, None, length=iters)
    a = assigns[-1]
    tie = jax.random.uniform(jax.random.fold_in(key, 1), (M,))
    order = jnp.lexsort((tie, a))
    return order


# ---------------------------------------------------------------------------
# diagnostics used by theory tests and EXPERIMENTS
# ---------------------------------------------------------------------------

def offdiag_mass(spec: kf.KernelSpec, x: Array, y: Array, perm: Array,
                 n_partitions: int) -> Array:
    """Q-bar of Theorem 1: sum of |Q_ij| over cross-partition pairs.

    O(M^2) — used on small/medium synthetic sets in tests and benches only.
    """
    xp, yp = x[perm], y[perm]
    Q = kf.signed_gram(spec, xp, yp)
    M = x.shape[0]
    m = M // n_partitions
    pid = jnp.arange(M) // m
    cross = pid[:, None] != pid[None, :]
    return jnp.sum(jnp.where(cross, jnp.abs(Q), 0.0))


def min_principal_angle(spec: kf.KernelSpec, x: Array, stratum: Array,
                        n_landmarks: int) -> Array:
    """cos(tau) estimate: max cross-stratum normalized kernel value."""
    K = kf.gram(spec, x)
    diag = jnp.sqrt(jnp.maximum(kf.gram_diag(spec, x), 1e-12))
    Kn = K / (diag[:, None] * diag[None, :])
    cross = stratum[:, None] != stratum[None, :]
    return jnp.max(jnp.where(cross, Kn, -jnp.inf))
