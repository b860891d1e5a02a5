"""``mla_decode_roofline`` on a small synthetic reduced trace: it reads only
the absorbed MLA decode kernel's events inside the traced window, nothing
without a trace or without that kernel, and its least time is the hand
count of ``decode_attn`` over the live slots' filled positions."""
from __future__ import annotations

import json

import pytest

from bench import harness

CELL = "minicpm3_4b.interp.chat"


@pytest.fixture(scope="module")
def reader():
    return harness.module("metrics", "mla_decode_roofline")


def _run(ops, steps, traced=True):
    cell = harness.load_cell(CELL)
    tr = {"devices": [{"ops": ops, "modules": []}],
          "host": [[1_000, 10_000_000, "traced_window"]]}
    return harness.Run(cell, 51.0, [], steps, 0.0,
                       harness.peaks("TPU v5 lite"),
                       harness.module("counts", cell.arch),
                       trace=tr if traced else None,
                       traced=(0.0, 1.0) if traced else None)


# one tick of two decode steps: a slot from 100 positions decodes 2
# tokens, one from 50 decodes 1; an admission of 300 in the same step
STEP = {"t0": 0.1, "t1": 0.2, "steps": 2, "live": [(100, 2), (50, 1)],
        "admitted": [300], "tokens": 4}
OPS = [[2_000, 50_000, "mla_flash_lib", None],
       [60_000, 30_000, "mla_flash_lib", [["bf16", [16, 40, 256]]]],
       [100_000, 700_000, "flash_lib", [["bf16", [160, 512, 96]]]],
       [20_000_000, 40_000, "mla_flash_lib", None]]  # after the window


def test_least_time_is_the_hand_count_over_kernel_time(reader):
    cfg = json.loads((harness.ROOT / "bench" / "configs"
                      / "minicpm3_4b.interp.json").read_text())
    h, kvl, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                    cfg["qk_rope_head_dim"])
    pk = harness.peaks("TPU v5 lite")

    def call(ns):  # one layer's call over the positions each slot attends
        flops = sum(2 * h * n * (kvl + rope) + 2 * h * n * kvl for n in ns)
        nbytes = sum(2 * (n * (kvl + rope) + h * (kvl + rope) + h * kvl)
                     for n in ns)
        return max(flops / pk["bf16_flops_per_s"],
                   nbytes / pk["hbm_bytes_per_s"])

    least = cfg["num_hidden_layers"] * (call([101, 51]) + call([102]))
    got = reader.read(_run(OPS, [STEP]))
    assert got == pytest.approx(100.0 * least / 80e-6, rel=1e-12)
    assert 0 < got <= 100


def test_reads_nothing_without_a_trace_or_the_kernel(reader):
    assert reader.read(_run(OPS, [STEP], traced=False)) is None
    # a program that decodes MLA in the expanded form runs no such kernel
    assert reader.read(_run([o for o in OPS if o[2] != "mla_flash_lib"],
                            [STEP])) is None


def test_the_cell_and_metric_are_declared():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    metric = {m["name"]: m for m in bench["per_layer"]}["mla_decode_roofline"]
    assert metric["workloads"] == [CELL]
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.arch == "minicpm3"
    assert "mla_decode_roofline" in [m["name"] for m in cell.per_layer]
