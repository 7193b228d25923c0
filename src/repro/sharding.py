"""Logical-axis sharding: map semantic array axes onto mesh axes.

Every parameter / activation in the framework is annotated with a tuple of
*logical* axis names (one per array dim, None for unsharded). At lowering
time :func:`logical_to_spec` resolves them to a PartitionSpec under the
active rule set, with a divisibility fallback: if a dim does not divide by
the mesh axis it would shard over, it is replicated instead (e.g.
smollm-135m's 9 heads on a 16-way model axis).

Default rules (ZeRO-3/FSDP flavored, MaxText-style):

  batch    -> ("pod", "data")    activations' batch dim
  embed    -> "data"             d_model param dim (FSDP; XLA all-gathers)
  mlp      -> "model"            d_ff / experts' hidden
  heads    -> "model"            attention heads (q)
  kv_heads -> "model"            attention kv heads
  vocab    -> "model"            embedding/output vocab dim
  experts  -> "model"            MoE expert dim (EP)
  kv_seq   -> "model"            decode KV-cache sequence dim (32k/500k
                                 decode shards the cache by sequence)
  layers / repeats / conv / stack / head_dim / qk / None -> replicated

The rule table is plain data so perf iterations can swap rule sets
(EXPERIMENTS §Perf ablates embed->None vs embed->data, kv_seq->data, ...).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = tuple[Any, ...]       # tuple of logical names (str | None) per dim


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (the sharding mode all
    of this repo's meshes use)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "vocab": "model",
    "experts": "model",
    "kv_seq": "model",
    "seq": None,
    "layers": None,
    "repeats": None,
    "stack": None,
    "head_dim": None,
    "conv": None,
    "state": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A rule table plus the mesh it resolves against."""

    rules: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))

    def replace(self, **kv) -> "ShardingRules":
        r = dict(self.rules)
        r.update(kv)
        return ShardingRules(rules=r)


def _axis_size(mesh: Mesh, mesh_axes) -> int:
    if mesh_axes is None:
        return 1
    if isinstance(mesh_axes, str):
        return mesh.shape.get(mesh_axes, 1)
    n = 1
    for a in mesh_axes:
        n *= mesh.shape.get(a, 1)
    return n


def logical_to_spec(axes: Axes, shape: Sequence[int], mesh: Mesh,
                    rules: ShardingRules | None = None) -> P:
    """Resolve logical axes to a PartitionSpec with divisibility fallback."""
    rules = rules or ShardingRules()
    used: set[str] = set()
    parts = []
    for dim, name in zip(shape, axes):
        if name is None:
            parts.append(None)
            continue
        mesh_axes = rules.rules.get(name)
        if mesh_axes is None:
            parts.append(None)
            continue
        tup = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
        # only mesh axes that exist on this mesh, are unused so far, and
        # divide the dim
        eff = []
        size = 1
        for a in tup:
            if a in mesh.shape and a not in used:
                eff.append(a)
                size *= mesh.shape[a]
        if eff and dim % size == 0:
            parts.append(tuple(eff) if len(eff) > 1 else eff[0])
            used.update(eff)
        else:
            parts.append(None)       # divisibility / availability fallback
    # strip trailing Nones for cleanliness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(axes_tree, shape_tree, mesh: Mesh,
                   rules: ShardingRules | None = None):
    """Map a pytree of logical-axes tuples + matching shapes (or arrays /
    ShapeDtypeStructs) to a pytree of NamedShardings."""
    def one(axes, arr):
        shape = arr.shape if hasattr(arr, "shape") else tuple(arr)
        return NamedSharding(mesh, logical_to_spec(axes, shape, mesh, rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def tree_specs(axes_tree, shape_tree, mesh: Mesh,
               rules: ShardingRules | None = None):
    """Same as tree_shardings but returns raw PartitionSpecs (for in_shardings)."""
    def one(axes, arr):
        shape = arr.shape if hasattr(arr, "shape") else tuple(arr)
        return logical_to_spec(axes, shape, mesh, rules)
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


# -- active-mesh context ------------------------------------------------------
# The launcher (repro.launch.*) installs the mesh + rules here; model code
# calls ``constrain`` freely and it is a no-op when no mesh is active (CPU
# smoke tests), so the same model code serves tests and production lowering.

_ACTIVE: dict = {"mesh": None, "rules": None}


def set_mesh(mesh: Mesh | None, rules: ShardingRules | None = None) -> None:
    _ACTIVE["mesh"] = mesh
    _ACTIVE["rules"] = rules


class use_mesh:
    """Context manager: with sharding.use_mesh(mesh, rules): ..."""

    def __init__(self, mesh: Mesh | None, rules: ShardingRules | None = None):
        self._new = (mesh, rules)
        self._old = (None, None)

    def __enter__(self):
        self._old = (_ACTIVE["mesh"], _ACTIVE["rules"])
        set_mesh(*self._new)
        return self

    def __exit__(self, *exc):
        set_mesh(*self._old)
        return False


def constrain(x: jax.Array, axes: Axes,
              rules: ShardingRules | None = None) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op when no active mesh)."""
    mesh = _ACTIVE["mesh"]
    if mesh is None:
        return x
    rules = rules or _ACTIVE["rules"]
    spec = logical_to_spec(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
