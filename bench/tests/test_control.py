"""The precision control at a small size: the program's matrix products
one step below ``HIGHEST`` (emulated on the CPU as the TPU computes them)
must come out not correct. Each run is a process of its own, since the
control changes the process's lowering of matrix products.

The streamed DSVRG fit reads the same at ``HIGH`` as at ``HIGHEST`` (its
margins over 18 features lose nothing measurable at three bf16 passes;
PERF.md gives the chip's readings), so its control is one bf16 pass.
"""
import json
import os
import subprocess
import sys

import pytest

from harness import cells

import tiny

SCRIPT = """
import json, sys
sys.path[:0] = {paths!r}
import control
control.lower_precision({to!r})
import tiny
print(json.dumps(tiny.run({name!r})["checks"]))
"""


CONTROL = {"cod-rna-rbf.fit": "HIGH", "susy-linear.fit": "DEFAULT"}


@pytest.mark.parametrize("name", list(tiny.TINY))
def test_control_is_not_correct(name):
    here = os.path.dirname(__file__)
    paths = [here, str(cells.BENCH), str(cells.ROOT / "src")]
    p = subprocess.run([sys.executable, "-c",
                        SCRIPT.format(paths=paths, name=name,
                                      to=CONTROL[name])],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    checks = json.loads(p.stdout.strip().splitlines()[-1])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


KERNEL_PRECISIONS = """
import functools, json, sys
sys.path[:0] = {paths!r}
import control
if {lower!r}:
    control.lower_fused_cd_pass("HIGH")
import jax, jax.numpy as jnp
from repro.api import ProblemSpec
from repro.core import engines
from repro.kernels import ops
ops._INTERPRET = False          # trace the chip's path: the fused pass


def calls(jaxpr, kernel):
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            yield kernel, str(e.params["precision"][0]).split(".")[-1]
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(sub, "jaxpr", sub)
                if hasattr(j, "eqns"):
                    name = kernel
                    if e.primitive.name == "pallas_call":
                        name = e.params["jaxpr"].debug_info.func_name
                    yield from calls(j, name)


p = ProblemSpec.create("rbf", gamma=0.5, lam=100.0, theta=0.1, ups=0.5)
solve = functools.partial(
    engines.make_local_solver("pallas", block=128, gram_threshold=64),
    spec=p.kernel, params=p.params, tol=1e-4, max_sweeps=5)
K, m, d = 2, 256, 8
jx = jax.make_jaxpr(solve)(jnp.zeros((K, m, d)), jnp.ones((K, m)),
                           jnp.zeros((K, 2 * m)))
print(json.dumps(sorted(set(calls(jx.jaxpr, "")))))
"""


@pytest.mark.parametrize("lower", [False, True])
def test_fused_only_control_lowers_that_kernel_alone(lower):
    """On the CPU the level solve takes the two-launch path, so the fused
    CD pass runs only on the chip (PERF.md has its readings there); here
    its traced program shows which products the control lowers."""
    paths = [str(cells.BENCH), str(cells.ROOT / "src")]
    p = subprocess.run([sys.executable, "-c", KERNEL_PRECISIONS.format(
        paths=paths, lower=lower)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {tuple(x) for x in json.loads(p.stdout.strip().splitlines()[-1])}
    fused = "HIGH" if lower else "HIGHEST"
    assert {x for x in got if x[0] == "_fused_mf_kernel"} == {
        ("_fused_mf_kernel", fused)}
    assert {x for x in got if x[0] != "_fused_mf_kernel"} <= {
        ("", "HIGHEST"), ("_gram_matvec_kernel", "HIGHEST")}
    assert ("_gram_matvec_kernel", "HIGHEST") in got
