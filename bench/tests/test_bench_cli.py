"""The command exits non-zero and prints no result where it cannot measure:
on a machine without a TPU, and in a directory that holds only the
benchmark's own files."""
import os
import subprocess
import sys

from bench.tests.conftest import ROOT, make_copy

ARGS = ["--workload", "hpl_dense_n16384.fresh", "--seed", str(2**31 + 5), "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    p = _run(make_copy(str(tmp_path)))
    assert p.returncode != 0
    assert "{" not in p.stdout
