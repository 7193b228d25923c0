"""Rehearsal: each cell's device programs compiled at the cell's real
shapes for one chip of a described TPU v5e, with no chip attached.
Nothing runs; a compile that passes is not a chip run. The topology is
described inside a fixture, never at import."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from harness import cells

F32 = jnp.float32
FIT = cells.load("cod-rna-rbf.fit").config
STREAM = cells.load("susy-linear.fit").config


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from repro.kernels import ops
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # compile the kernels for the chip, not for the CPU's interpreter
        mp.setattr(ops, "_INTERPRET", False)
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


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)


def _levels():
    from data import blobs
    d = FIT["data"]
    M = blobs.train_rows(d["rows"], d["train_fraction"])
    s = FIT["solver"]
    return [(s["p"] ** L, M // s["p"] ** L, d["features"])
            for L in range(s["levels"], -1, -1)]


def _level_solver():
    from repro.api import ProblemSpec
    from repro.core import engines
    odm = FIT["odm"]
    problem = ProblemSpec.create("rbf", gamma=0.7, lam=odm["lam"],
                                 theta=odm["theta"], ups=odm["ups"])
    s = FIT["solver"]
    body = functools.partial(engines.make_local_solver(s["engine"]),
                             spec=problem.kernel, params=problem.params,
                             tol=s["tol"], max_sweeps=s["max_sweeps"])
    return body


@pytest.mark.parametrize("level", range(4))
def test_fit_level_solve_one_chip(topo, level):
    K, m, d = _levels()[level]
    one = SingleDeviceSharding(topo.devices[0])
    text = jax.jit(_level_solver()).lower(
        _spec((K, m, d), one), _spec((K, m), one),
        _spec((K, 2 * m), one)).compile().as_text()
    assert "tpu_custom_call" in text


def test_stream_steps(topo):
    from repro.core import dsvrg
    from repro.core.odm import ODMParams
    one = SingleDeviceSharding(topo.devices[0])
    ds = STREAM["solver"]["dsvrg"]
    b, d = ds["batch"], STREAM["data"]["features"]
    R = -(-ds["stream_slab"] // b) * b
    stats, inner = dsvrg._make_stream_steps(ODMParams(**STREAM["odm"]), b,
                                            True)
    vec = _spec((d,), one)
    t1 = stats.lower(vec, _spec((R, d), one), _spec((R,), one),
                     _spec((R,), one),
                     M=STREAM["data"]["rows"]).compile().as_text()
    t2 = inner.lower(vec, vec, vec, _spec((), one),
                     _spec((R // b, b, d), one), _spec((R // b, b), one),
                     _spec((R // b, b), one)).compile().as_text()
    assert "tpu_custom_call" in t1 and "tpu_custom_call" in t2

