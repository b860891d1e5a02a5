"""Fixtures of the benchmark's CPU tests: the repository root on the import
path (``import bench``) and a throwaway checkout holding the benchmark's
files plus the smoke-size cells of ``data/smoke``."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def smoke_root(tmp_path) -> pathlib.Path:
    """A checkout whose BENCHMARK.json lists the smoke cells; the bench
    code is copied in, the peaks table also knows the CPU (for the
    arithmetic only: no device metric is reported from a CPU run)."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(DATA / "smoke", tmp_path, dirs_exist_ok=True)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    smoke = json.loads((tmp_path / "BENCHMARK.json").read_text())
    smoke["end_to_end"] = real["end_to_end"]
    smoke["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in real["per_layer"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(smoke))
    peaks = json.loads((tmp_path / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return tmp_path
