"""Compiled ODM inference artifacts (the deployable model).

The ODM decision function is a kernel expansion over the dual,
f(x) = sum_i y_i (zeta_i - beta_i) kappa(x_i, x). Serving it from the raw
solver output re-reads the *entire* training set per request batch; this
module compiles the expansion ONCE into a :class:`FittedODM`:

* **SV pruning** — complementary slackness puts instances whose margin
  lies inside the [1-theta, 1+theta] band at exactly zero dual, so
  coefficients with |y·(zeta-beta)| <= ``prune_tol`` are dropped and the
  survivors packed into a contiguous (S, d) slab (a single O(M·d) gather
  at compile time, never per request).
* **Linear collapse** — for the linear kernel the expansion telescopes to
  an explicit primal ``w = X_svᵀ coef``: O(d) scoring, no slab at all.
  The DSVRG engine's output is born in this form.
* **Nyström landmark compression** — when the SV slab exceeds a budget,
  the expansion is projected onto ``L`` landmark functions
  kappa(z_l, ·): coefficients c = (K_zz + eps I)⁻¹ K_zs coef. The
  landmarks are picked by :func:`repro.core.partition.select_landmarks`
  — the paper's Eqn. 8 pivoted-Cholesky greedy IS Nyström pivot
  selection (largest posterior variance first), so the partitioning
  machinery doubles as the compression machinery. An optional accuracy
  ``target`` (max |f_compressed − f_exact| over a probe set) grows the
  budget geometrically until met.

Scoring routes through the tiled matrix-free kernel
(:func:`repro.kernels.ops.decision_scores`): one ``pallas_call`` per
request batch, O(B·S_block) memory, never a dense (T, S) Gram.
``save``/``load_model`` persist through
:class:`repro.distributed.checkpoint.CheckpointManager` (atomic commit,
versioned steps), with the kernel spec and compression provenance in the
manifest metadata.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernel_fns as kf
from repro.core import odm as odm_mod
from repro.core import partition as part_mod
from repro.kernels import ops
from repro.precision import matmul

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FittedODM:
    """A compiled, deployable ODM model.

    Exactly one representation is populated:

    * ``w`` (d,)            — explicit primal weights (linear kernel);
    * ``x_sv`` (S, d) + ``coef`` (S,) — packed kernel expansion.

    ``n_train`` is the source expansion size M, ``compression`` one of
    ``"exact" | "pruned" | "nystrom" | "linear"``, ``gap`` the estimated
    max |f_model − f_exact| over the compile-time probe set (0.0 for the
    lossless routes: exact, pruned-at-zero-tol and linear collapse).
    """

    spec: kf.KernelSpec
    w: Array | None = None
    x_sv: Array | None = None
    coef: Array | None = None
    n_train: int = 0
    compression: str = "exact"
    gap: float = 0.0

    @property
    def n_sv(self) -> int:
        """Support vectors actually scored against (0 for linear w)."""
        return 0 if self.x_sv is None else int(self.x_sv.shape[0])

    # -- scoring ------------------------------------------------------------

    def decision_function(self, x: Array, *, bt: int = 256, bs: int = 256,
                          tiled: bool | None = None) -> Array:
        """f(x) (T,) through the serving path: O(d) matvec for linear,
        the tiled matrix-free scorer otherwise (``tiled`` as in
        :func:`repro.kernels.ops.decision_scores`)."""
        if self.w is not None:
            return matmul(x, self.w)
        return ops.decision_scores(x, self.x_sv, self.coef, self.spec,
                                   bt=bt, bs=bs, tiled=tiled)

    def predict(self, x: Array, **kw) -> Array:
        return jnp.sign(self.decision_function(x, **kw))

    # -- persistence --------------------------------------------------------

    def save(self, directory: str) -> str:
        """Atomic versioned save (CheckpointManager step 0)."""
        from repro.distributed.checkpoint import CheckpointManager
        tree = {k: v for k, v in (("w", self.w), ("x_sv", self.x_sv),
                                  ("coef", self.coef)) if v is not None}
        meta = {
            "kind": "fitted_odm",
            "spec": dataclasses.asdict(self.spec),
            "n_train": self.n_train,
            "compression": self.compression,
            "gap": float(self.gap),
        }
        return CheckpointManager(directory, keep=1).save(0, tree, meta)


def load_model(directory: str) -> FittedODM:
    """Exact round-trip of :meth:`FittedODM.save`."""
    from repro.distributed.checkpoint import CheckpointManager
    mgr = CheckpointManager(directory, keep=1)
    manifest = mgr.metadata()
    meta = manifest["metadata"]
    if meta.get("kind") != "fitted_odm":
        raise ValueError(f"{directory!r} does not hold a FittedODM "
                         f"checkpoint (kind={meta.get('kind')!r})")
    template = {k: jax.ShapeDtypeStruct(tuple(v["shape"]), v["dtype"])
                for k, v in manifest["leaves"].items()}
    tree = mgr.restore(template)
    spec = kf.KernelSpec(**meta["spec"])
    return FittedODM(spec=spec, w=tree.get("w"), x_sv=tree.get("x_sv"),
                     coef=tree.get("coef"), n_train=int(meta["n_train"]),
                     compression=meta["compression"],
                     gap=float(meta["gap"]))


# ---------------------------------------------------------------------------
# compilation: solver output -> artifact
# ---------------------------------------------------------------------------

def compile_model(spec: kf.KernelSpec, x_train: Array, y_train: Array,
                  alpha: Array, *, prune_tol: float = 0.0,
                  budget: int | None = None, target: float | None = None,
                  ) -> FittedODM:
    """Compile a dual solution into a deployable :class:`FittedODM`.

    ``alpha`` (2M,) is any solver's [zeta; beta]. ``prune_tol`` drops
    coefficients with |y·(zeta−beta)| <= tol (0.0 prunes the exact zeros
    complementary slackness guarantees — lossless). ``budget``/``target``
    enable Nyström compression of nonlinear kernels (see module docs);
    the linear kernel always collapses to an explicit ``w`` instead.
    """
    M = x_train.shape[0]
    zeta, beta = odm_mod.split_alpha(alpha)
    coef = y_train * (zeta - beta)                          # (M,)
    keep = np.nonzero(np.abs(np.asarray(coef)) > prune_tol)[0]
    if keep.size == 0:
        keep = np.array([0])                 # degenerate: all-zero dual
    idx = jnp.asarray(keep)
    x_sv = jnp.take(x_train, idx, axis=0)
    c_sv = jnp.take(coef, idx)

    if spec.name == "linear":
        # pruning is lossless here whatever the tol: the dropped
        # coefficients are folded into w exactly by re-deriving it from
        # the FULL expansion
        w = (matmul(x_train.T, coef) if prune_tol > 0.0
             else matmul(x_sv.T, c_sv))
        return FittedODM(spec=spec, w=_unplaced(w), n_train=M,
                         compression="linear")

    compression = "exact" if keep.size == M and prune_tol == 0.0 \
        else "pruned"
    model = FittedODM(spec=spec, x_sv=_unplaced(x_sv), coef=_unplaced(c_sv),
                      n_train=M, compression=compression)
    if prune_tol > 0.0 and keep.size < M:
        # lossy pruning: measure the decision gap it introduced so the
        # reported provenance (and compress()'s cumulative gap) is honest
        probe = x_train[:_PROBE_CAP]
        full = FittedODM(spec=spec, x_sv=x_train, coef=coef, n_train=M)
        model = dataclasses.replace(
            model, gap=decision_gap(model, full, probe))
    if budget is not None and model.n_sv > budget:
        model = compress(model, budget, target=target)
    return model


def _unplaced(a: Array) -> Array:
    """An uncommitted copy of ``a`` on the default device. A fit on a mesh
    leaves its duals replicated over the mesh; the artifact must not keep
    that placement, because serving places it itself (one device, or
    sharded by ``score_sharded``) and a Pallas kernel cannot be
    partitioned across devices automatically."""
    return jnp.asarray(np.asarray(a))


def from_sodm(spec: kf.KernelSpec, res, x_train: Array, y_train: Array,
              **kw) -> FittedODM:
    """Compile an ``SODMResult`` — applies ``res.perm`` exactly once."""
    return compile_model(spec, x_train[res.perm], y_train[res.perm],
                         res.alpha, **kw)


def from_dsvrg(res) -> FittedODM:
    """A ``DSVRGResult`` is born compressed: linear kernel, explicit w.

    For direct ``dsvrg.solve`` consumers; the SODM engine route
    (``SODMConfig.engine="dsvrg"``) reaches :func:`from_sodm` through the
    recovered dual and collapses to the identical ``w``.
    """
    return FittedODM(spec=kf.KernelSpec(name="linear"), w=res.w,
                     n_train=int(res.perm.shape[0]), compression="linear")


def from_cascade(spec: kf.KernelSpec, res, **kw) -> FittedODM:
    """Compile a cascade baseline's survivor set (``CascadeResult``)."""
    return compile_model(spec, res.x_sv, res.y_sv, res.alpha, **kw)


# ---------------------------------------------------------------------------
# Nyström landmark compression
# ---------------------------------------------------------------------------

_PROBE_CAP = 512      # decision-gap probe rows (SV subsample)
_JITTER = 1e-8


def _nystrom(spec: kf.KernelSpec, x_sv: Array, coef: Array,
             budget: int) -> tuple[Array, Array]:
    """Project the expansion onto ``budget`` landmark functions.

    min_c ||sum_l c_l k(z_l, ·) − sum_s coef_s k(x_s, ·)||²_RKHS has the
    normal equations K_zz c = K_zs coef; the landmarks are the pivoted-
    Cholesky picks of Eqn. 8 (max posterior variance), the standard
    Nyström pivot rule.
    """
    picks = part_mod.select_landmarks(spec, x_sv, budget)
    z = jnp.take(x_sv, picks, axis=0)
    kzz = kf.gram(spec, z)
    kzs = kf.gram(spec, z, x_sv)
    eye = jnp.eye(budget, dtype=kzz.dtype)
    c = jnp.linalg.solve(kzz + _JITTER * budget * eye, matmul(kzs, coef))
    return z, c


def decision_gap(model: FittedODM, other: FittedODM, probe: Array) -> float:
    """max |f_model(probe) − f_other(probe)| (dense oracle on both sides)."""
    a = model.decision_function(probe, tiled=False)
    b = other.decision_function(probe, tiled=False)
    return float(jnp.max(jnp.abs(a - b)))


def compress(model: FittedODM, budget: int, *, target: float | None = None,
             probe: Array | None = None) -> FittedODM:
    """Nyström-compress an expansion model down to <= ``budget`` landmarks.

    With ``target`` set, the budget is doubled until the decision gap on
    ``probe`` (default: up to 512 SV rows) is <= target or the budget
    reaches the SV count (at which point compression is pointless and the
    input model is returned unchanged).
    """
    if model.x_sv is None:
        return model                       # linear w: already O(d)
    S = model.n_sv
    if budget >= S:
        return model
    if probe is None:
        probe = model.x_sv[:_PROBE_CAP]
    while True:
        z, c = _nystrom(model.spec, model.x_sv, model.coef, budget)
        cand = dataclasses.replace(model, x_sv=z, coef=c,
                                   compression="nystrom")
        gap = decision_gap(cand, model, probe)
        if target is None or gap <= target or budget * 2 >= S:
            break
        budget *= 2
    if target is not None and gap > target and budget * 2 >= S:
        return model                       # budget search exhausted
    return dataclasses.replace(cand, gap=model.gap + gap)
