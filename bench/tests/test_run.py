"""The command prints no result without a chip, or without the program."""

import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "powit-n1-steady", "--seed", str(2**35 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          capture_output=True, text=True, env=env,
                          timeout=300)


def test_no_tpu_no_result():
    out = _run(ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
