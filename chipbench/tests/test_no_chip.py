"""Without an accelerator, or without the program, a run fails and prints
no result."""
import os
import shutil
import subprocess
import sys

from conftest import CHIPBENCH


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "whisper-base.train-dmd", "--seed", str(2 ** 40 + 3), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cpu_run_fails_without_a_result():
    r = _run(CHIPBENCH.parent)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copytree(CHIPBENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(CHIPBENCH.parent / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
