"""The measurement path refuses to run without a TPU, and without the
program beside it: a non-zero exit and no result line."""

import os
import shutil
import subprocess
import sys

from _bench_helpers import CHIP, ROOT

ARGS = ["--workload", "cp3-f32.cube1024", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT, str(CHIP / "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_bare_benchmark_directory_means_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    rel = CHIP.relative_to(ROOT)
    shutil.copytree(CHIP, tmp_path / rel,
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, str(tmp_path / rel / "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
