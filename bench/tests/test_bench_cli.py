"""run_cell.py refuses to measure without a chip: a non-zero exit and no
result line, on the CPU and in a directory that holds only the
benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "hist-solid", "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run_cell.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            continue
    return False


def test_cpu_backend_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "not a TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
