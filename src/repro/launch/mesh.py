"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
data-parallel by default (gradient all-reduce across pods over DCN/ICI).
"""
from __future__ import annotations

import jax

from repro.sharding import make_mesh

#: ``device_kind`` of the chips the production meshes stand for. The dry
#: run builds them from host CPU devices, so its roofline takes the peaks
#: of this kind (``hlo_analysis.hardware``), not of the devices at hand.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over host devices for CI-scale distributed tests."""
    return make_mesh(shape, axes)
