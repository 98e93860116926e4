"""`bench/run.py` prints no result and exits non-zero without a TPU, and in
a directory that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "telemetry.dash", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_no_result():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_bare_directory_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
