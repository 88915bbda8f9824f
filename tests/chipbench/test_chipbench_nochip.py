"""A run that cannot measure exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "paper-regular100.fig1-decafork", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_run_without_a_chip_fails_before_any_work():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
