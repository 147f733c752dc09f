"""``run.py`` prints no result and exits non-zero without a TPU, and in a
directory that holds only BENCHMARK.json and the benchmark's own files."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "train_gpt2_medium_1chip", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_a_machine_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "*.xplane.pb"),
    )
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "nope", "--seed",
         "1", "--seconds", "1"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
