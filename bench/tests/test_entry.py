"""The entry point never falls back: without a TPU, or outside a
checkout of the repository, it exits non-zero and prints no result."""
import os
import subprocess
import sys

from bench.tests.conftest import REPO

ARGS = ["--workload", "mamba2-780m.chat_b4", "--seed", "1", "--seconds",
        "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(REPO)
    assert p.returncode == 3, p.stderr
    assert p.stdout.strip() == ""
    assert "No fallback" in p.stderr


def test_benchmark_files_alone_no_result(bench_copy):
    p = _run(bench_copy)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
