"""The entry point refuses to run where it cannot measure: without a TPU it
prints no result and exits with code 2, and a directory that holds only
BENCHMARK.json and bench/ (no program) prints no result and fails."""
import os
import shutil
import subprocess
import sys

from bench import run


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bad51.drain",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(run.ROOT, env)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "refusing" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(run.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
