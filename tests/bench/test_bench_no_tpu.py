"""The command fails loudly without a TPU, with no CPU fallback, and in a
tree that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from tiny_bench import REPO

ARGS = ["--workload", "mnist_cnn.grid_k16", "--seed", "2147483701",
        "--seconds", "1", "--trace", "0"]


def run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_no_tpu_no_result():
    p = run(REPO)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "TPU" in p.stderr and "Nothing was run" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = run(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
