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
    """``shapes`` are float32 shapes, or whole ``ShapeDtypeStruct``s."""
    args = [s if isinstance(s, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(s, F32, sharding=sharding)
            for s in shapes]
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


@pytest.mark.parametrize("K, nblk", [(4, 47), (1, 186)])
def test_fused_cd_pass_ragged_groups(one_chip, K, nblk):
    """The fit cell's levels whose tile count is no multiple of the
    lockstep group (cod-rna, d = 8: K = 4 partitions of 47 tiles, K = 1
    of 186): the last group's missing tiles clamp their blocks."""
    B, d = 256, 8
    src = lambda x, y: gram.KernelSource(kind="rbf", x=x, y=y, gamma=0.7,
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


@pytest.mark.parametrize("phase, K, gathers", [
    ("sharded", 8, False), ("handoff", 2, True), ("replicated", 1, False)])
def test_mesh_cell_level_solves(topo, monkeypatch, phase, K, gathers):
    """The four-chip fit cell's level solves at cod-rna's shapes (47,616
    training rows, d = 8): K = 8 sharded, two partitions of 5,952 rows a
    chip; K = 2 replicated, 23,808 rows, entered with the duals still
    spread over the chips as the merge leaves them (2 x 2 over the
    partitions and their rows), so the compiler gathers them at the
    hand-off; K = 1 replicated, 47,616 rows, on replicated inputs. Each
    keeps the kernel inside its shard_map."""
    import re

    from repro.core import engines, kernel_fns as kf, odm, sodm
    monkeypatch.setattr(ops, "_INTERPRET", False)
    devices = np.asarray(topo.devices)
    mesh = Mesh(devices, ("data",))
    body = functools.partial(
        engines.make_local_solver("pallas"),
        spec=kf.KernelSpec("rbf", gamma=0.7),
        params=odm.ODMParams(lam=100.0, theta=0.1, ups=0.5), tol=1e-4,
        max_sweeps=200)
    sharded, replicated = sodm.mesh_level_solves(body, mesh, "data")
    m = 47616 // K
    rows = NamedSharding(mesh, P("data") if phase == "sharded" else P())
    duals = NamedSharding(Mesh(devices.reshape(2, 2), ("a", "b")),
                          P("a", "b")) if phase == "handoff" else rows
    fn = sharded if phase == "sharded" else replicated
    text = fn.lower(jax.ShapeDtypeStruct((K, m, 8), F32, sharding=rows),
                    jax.ShapeDtypeStruct((K, m), F32, sharding=rows),
                    jax.ShapeDtypeStruct((K, 2 * m), F32, sharding=duals)
                    ).compile().as_text()
    assert "tpu_custom_call" in text
    gather = re.compile(r"^\s*%all-gather[\w.-]* = .* all-gather\(", re.M)
    assert bool(gather.search(text)) == gathers


@pytest.mark.parametrize("kernel, instruction", [
    # named after its enclosing while body; found by its output signature,
    # the (K, nblk, 2, B) duals and the (K, nblk, B) product
    ("fused_cd_pass",
     r"%body\.\d+ = \(f32\[8,24,2,256\]\S*, f32\[8,24,256\]"),
    # named after its jitted wrapper
    ("odm_svrg_grad", r"%odm_svrg_grad\.\d+ = f32\[1,18\]"),
])
def test_kernel_instruction_inside_the_program(one_chip, monkeypatch, kernel,
                                               instruction):
    """Each kernel sits in a while loop's body of its program (a level
    solve at the fit cell's K = 8 level, the streamed inner slab program
    over one 8,192-row slab); the device trace names an operation by its
    HLO instruction, which the benchmark's reduction matches by the forms
    pinned here."""
    import re

    from repro.core import dsvrg, engines, kernel_fns as kf, odm
    monkeypatch.setattr(ops, "_INTERPRET", False)
    if kernel == "fused_cd_pass":
        fn = jax.jit(functools.partial(
            engines.make_local_solver("pallas"),
            spec=kf.KernelSpec("rbf", gamma=0.7),
            params=odm.ODMParams(lam=100.0), tol=1e-4, max_sweeps=200))
        K, m = 8, 5952
        shapes = [(K, m, 8), (K, m), (K, 2 * m)]
    else:
        _, fn = dsvrg._make_slab_steps(
            *dsvrg._make_stream_steps(odm.ODMParams(lam=100.0), 512, True),
            8192, 16, 5_000_000)
        shapes = [(18,), (18,), (18,), (), (8192, 18), (8192,),
                  jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    text = _compiled_text(fn, one_chip, *shapes)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and re.search(instruction, ln)]
    assert len(calls) == 1, kernel
