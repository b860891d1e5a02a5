"""The trace reduction on a small trace recorded on a TPU v5e: two fused
decode ticks (4 steps each) of a 2-layer Yi-6B-width model with
interp-fused numerics, 4 slots, host spans named ``step``."""
from __future__ import annotations

import pathlib

import pytest

from bench import trace
from bench.harness import module

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "v5e_tick.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(TRACE))


@pytest.fixture(scope="module")
def span(tr):
    d = tr["devices"][0]
    return (min(o[0] for o in d["ops"]),
            max(o[0] + o[1] for o in d["ops"]))


def test_load_keeps_device_ops_modules_and_host_spans(tr):
    assert [d["name"] for d in tr["devices"]] == ["/device:TPU:0"]
    d = tr["devices"][0]
    assert len(d["ops"]) == 3302
    assert [m[2] for m in d["modules"]] == ["jit_tick", "jit_tick"]
    assert [h[2] for h in tr["host"]] == ["step", "step"]


def test_busy_is_the_union_of_nested_ops(tr, span):
    d = tr["devices"][0]
    lo, hi = span
    busy = trace.busy_ns(d, lo, hi)
    assert 0 < busy <= hi - lo
    # self times partition the busy time: nesting is not counted twice
    assert sum(trace.self_times(d, lo, hi).values()) == pytest.approx(busy)
    # clipping to half the span never adds time
    assert trace.busy_ns(d, lo, (lo + hi) / 2) <= busy


def test_module_time_counts_program_runs_starting_in_the_window(tr):
    d = tr["devices"][0]
    both = trace.module_time_ns(d, "jit_tick", 0, 1e12)
    assert both == 17598294.0 + 17599559.0
    assert trace.module_time_ns(d, "jit_admit", 0, 1e12) == 0


def test_kernels_are_found_by_their_operands(tr):
    d = tr["devices"][0]
    flash = module("metrics", "flash_lib_roofline").is_flash
    act = module("metrics", "act_lib_roofline").is_act
    # 2 ticks x 4 decode steps x 2 layers
    assert sum(flash(o[2], o[3]) for o in d["ops"]) == 16
    assert sum(act(o[2], o[3]) for o in d["ops"]) == 16
    t = trace.op_time_ns(d, flash, 0, 1e12)
    assert 0 < t < trace.module_time_ns(d, "jit_tick", 0, 1e12)


def test_idle_gaps_are_labelled_by_host_spans(tr, span):
    d = tr["devices"][0]
    lo, hi = span
    gaps = trace.idle_gaps(d, tr["host"], lo, hi, top=3)
    assert len(gaps) == 3
    assert gaps[0][0] == "step"  # between the two ticks the host steps
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1]


def test_window_needs_exactly_one_traced_window_span(tr):
    with pytest.raises(ValueError):
        trace.window(tr)
    fake = {"host": tr["host"] + [[10.0, 5.0, "traced_window"]]}
    assert trace.window(fake) == (10.0, 15.0)
