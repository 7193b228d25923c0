"""The harness runs nothing where it finds no chip or no program."""
import os
import shutil
import subprocess
import sys

from harness import cells

ARGS = ["--workload", "cod-rna-rbf.fit", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _run(cells.ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(cells.SPEC, tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".traces",
                                                  ".probe", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
