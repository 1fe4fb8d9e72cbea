"""Without a TPU, or without the program beside it, the benchmark exits
non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(cwd: Path, cell: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    return subprocess.run(
        [sys.executable] + cmd[1:] + ["--workload", cell, "--seed", "3000000001",
                                      "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_run_exits_nonzero_without_a_result(cell):
    p = _run(ROOT, cell)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    p = _run(tmp_path, CELLS[0])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
