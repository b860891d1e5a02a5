"""A new traffic mix, cell and per-layer metric come from new files and an
appended ``workloads`` entry alone: no existing file is edited."""
from __future__ import annotations

import json
import time

from bench import harness

READER = '''"""Requests admitted per engine step in the window (a test metric)."""


def read(run):
    steps = run.window_steps()
    return sum(len(s["admitted"]) for s in steps) / max(1, len(steps))
'''


def test_new_mix_cell_and_metric_from_new_files(smoke_root):
    before = {p: p.read_bytes() for p in (smoke_root / "bench").rglob("*")
              if p.is_file()}
    b = smoke_root / "bench"
    mix = json.loads((b / "mixes" / "tiny.json").read_text())
    mix.update(name="short", output_tokens={"dist": "lognormal", "median": 4,
                                            "sigma": 0.3, "min": 2,
                                            "max": 8})
    (b / "mixes" / "short.json").write_text(json.dumps(mix))
    (b / "cells" / "yi_smoke.interp.short.json").write_text(json.dumps(
        {"rate": 6.0, "limits": {"max_logit_gap": 0.05}}))
    (b / "metrics" / "admits_per_step.py").write_text(READER)
    bench = json.loads((smoke_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "yi_smoke.interp.short",
                               "config": "yi_smoke.interp",
                               "traffic": "short", "chips": 1,
                               "why": "short answers"})
    bench["end_to_end"].append({"name": "admits_per_step", "unit": "req",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["yi_smoke.interp.short"]})
    (smoke_root / "BENCHMARK.json").write_text(json.dumps(bench))
    for p, data in before.items():
        assert p.read_bytes() == data  # nothing existing was edited
    r = harness.run("yi_smoke.interp.short", 11, 1.5, False,
                    time.perf_counter(), smoke_root)
    assert r["correct"] is True
    assert r["metrics"]["admits_per_step"]["value"] > 0
    assert r["metrics"]["admits_per_step"]["unit"] == "req"
    # the other cells do not report the new metric
    other = harness.load_cell("yi_smoke.exact.tiny", smoke_root)
    assert "admits_per_step" not in [m["name"] for m in other.end_to_end]
