"""Compile the main path's Pallas kernels for a TPU v5e, with no chip attached.

Interpret mode (every other kernel test) never applies the TPU lowering's
rules, so a kernel can pass every CPU test and still be refused by the
chip's compiler. These tests lower and compile each kernel of the SODM
train-and-serve path with ``interpret=False`` for a *described* v5e chip,
at the shapes ``chip_smoke.py`` runs: ijcnn1 (d = 22) for the Gram, fused
dual-CD and scoring kernels, SUSY (d = 18) for the DSVRG gradient. Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import dual_cd_block, gram, odm_grad, ops, score

F32 = jnp.float32
CD = dict(c=0.5, ups=0.5, theta=0.1, mscale=113344.0, n_steps=512,
          exit_tol=1e-6)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology; the persistent compile cache is off
    meanwhile (entries compiled for a described chip cannot be read back
    without one) and the compiler writes no log files."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, F32, sharding=sharding) for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("kind", ["rbf", "laplacian", "poly", "linear"])
def test_gram(one_chip, kind):
    _compiled_text(lambda x: gram.gram(x, x, kind=kind, gamma=0.3, bd=22),
                   one_chip, (4096, 22))


def test_gram_matvec(one_chip):
    """The matrix-free u refresh at the bottom level (K = 8 partitions of
    14,168 rows, padded to 14,336)."""
    _compiled_text(
        lambda x, g: gram.gram_matvec(x, x, g, kind="rbf", gamma=0.3, bd=22),
        one_chip, (8, 14336, 22), (8, 14336))


def test_fused_cd_pass_matrix_free(one_chip):
    """The top level's fused pass: one partition of 113,344 rows as 443
    tiles of 256, Gram tiles rebuilt from the features in the kernel."""
    K, nblk, B, d = 1, 443, 256, 22
    src = lambda x, y: gram.KernelSource(kind="rbf", x=x, y=y, gamma=0.3,
                                         bd=d)
    _compiled_text(
        lambda qb, a, u, v, x, y: dual_cd_block.fused_cd_pass(
            qb, src(x, y), a, u, v, **CD),
        one_chip, (K, nblk, B, B), (K, nblk, 2 * B), (K, nblk, B),
        (K, nblk, B), (K, nblk * B, d), (K, nblk * B))


def test_fused_cd_pass_dense(one_chip):
    """A materialized-Q level (partitions at or below gram_threshold)."""
    K, nblk, B = 4, 14, 256
    _compiled_text(
        lambda qb, a, u, v, q: dual_cd_block.fused_cd_pass(
            qb, gram.DenseSource(q), a, u, v, **CD),
        one_chip, (K, nblk, B, B), (K, nblk, 2 * B), (K, nblk, B),
        (K, nblk, B), (K, nblk * B, nblk * B))


def test_cd_block_sweep(one_chip):
    """The two-launch layout's sweep kernel."""
    _compiled_text(
        lambda q, a, u, v: dual_cd_block.cd_block_sweep(q, a, u, valids=v,
                                                        **CD),
        one_chip, (56, 256, 256), (56, 512), (56, 256), (56, 256))


def test_score_tiles(one_chip):
    """One 256-row request batch against a padded SV slab."""
    _compiled_text(
        lambda x, z, c: score.score_tiles(x, z, c, kind="rbf", gamma=0.3,
                                          bd=22),
        one_chip, (256, 22), (20224, 22), (20224,))


def test_odm_svrg_grad(one_chip):
    """The streamed DSVRG inner step: one 512-row SUSY minibatch."""
    _compiled_text(
        lambda w, a, h, x, y, wt, inv: odm_grad.odm_svrg_grad(
            w, a, h, x, y, wt, inv, s=100.0 / 0.81, bm=512),
        one_chip, (18,), (18,), (18,), (512, 18), (512,), (512,), (1, 1))


def test_odm_grad(one_chip):
    """The anchor pass over one 8,192-row slab."""
    _compiled_text(lambda w, x, y: odm_grad.odm_grad(w, x, y, bm=512),
                   one_chip, (18,), (8192, 18), (8192,))


@pytest.mark.parametrize("phase", ["sharded", "replicated"])
def test_mesh_level_solves(topo, monkeypatch, phase):
    """The SPMD cascade's two level-solve programs on the four-chip mesh:
    K = 8 partitions sharded over ``data`` (two per chip), and the
    replicated tail at K = 2. A Pallas kernel cannot be partitioned
    automatically, so both must hold it inside their shard_map."""
    from repro.core import engines, kernel_fns as kf, odm, sodm
    monkeypatch.setattr(ops, "_INTERPRET", False)   # ops read the CPU host
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    body = functools.partial(
        engines.make_local_solver("pallas"),
        spec=kf.KernelSpec("rbf", gamma=0.3), params=odm.ODMParams(lam=100.0),
        tol=1e-4, max_sweeps=200)
    sharded, replicated = sodm.mesh_level_solves(body, mesh, "data")
    fn, K, spec = {"sharded": (sharded, 8, P("data")),
                   "replicated": (replicated, 2, P())}[phase]
    m = 113344 // K
    sh = NamedSharding(mesh, spec)
    args = [jax.ShapeDtypeStruct(s, F32, sharding=sh)
            for s in ((K, m, 22), (K, m), (K, 2 * m))]
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
