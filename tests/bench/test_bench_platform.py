"""The entry point never falls back to the CPU and needs the system under
test: both cases exit non-zero and print no result line."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(root: pathlib.Path, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         workload, "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_real_cell_fails_on_a_non_tpu_platform():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = _run(ROOT, bench["workloads"][0]["name"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, bench["workloads"][0]["name"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
