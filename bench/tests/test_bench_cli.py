"""The command refuses what it cannot measure: no TPU, no program, no
such cell.  Each refusal exits non-zero and prints no result."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_copy  # noqa: E402


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ("--seed", "2147483653", "--seconds", "1", "--trace", "0")


@pytest.mark.parametrize("trace", ["0", "1"])
def test_exits_nonzero_without_a_tpu(trace):
    p = _run(bench_copy.ROOT, "--workload", "b1-r224-fp32.offline",
             *ARGS[:-1], trace)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    root = bench_copy.make(tmp_path, with_src=False)
    p = _run(root, "--workload", "b1-r224-fp32.offline", *ARGS)
    assert p.returncode != 0 and p.stdout == ""


def test_exits_nonzero_for_an_unknown_cell():
    p = _run(bench_copy.ROOT, "--workload", "no-such.cell", *ARGS)
    assert p.returncode == 2 and p.stdout == ""
    assert "no workload" in p.stderr
