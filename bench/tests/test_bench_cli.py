"""``bench/run.py`` without a chip: it exits non-zero and prints no result,
also in a directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gemini-secagg",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_no_result_without_a_tpu(where, tmp_path):
    cwd = ROOT
    if where == "benchmark_only":
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = tmp_path
    proc = _run(cwd)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
