"""The correctness comparison catches what it must, at smoke size on the
CPU: the control (the plain reference computed in fp8, the precision below
the configuration's bfloat16, put in the program's place) comes out not
correct, and so does a run whose served tokens are altered where the fused
tick produces them."""
from __future__ import annotations

import time

import pytest

from bench import correctness, harness, traffic
from repro.serve import aot
from repro.serve import engine as engine_mod

CELL = "yi_smoke_bf16.interp.tiny"


def _finished(root, seed):
    cell = harness.load_cell(CELL, root)
    params = harness.make_weights(cell, seed)
    eng = harness.build_engine(cell, params, root / "tables")
    harness.warm_up(eng, cell, cell.hf["vocab_size"], seed)
    sched = traffic.arrivals(cell.mix, cell.params["rate"], 1.5, seed,
                             cell.hf["vocab_size"])
    recs, _, _, tainted = harness.drive(eng, sched, 1.5)
    assert tainted is None
    return cell, [r for r in recs if r.done_t is not None]


def test_fp8_control_is_not_correct_where_the_program_is(smoke_root):
    cell, fin = _finished(smoke_root, 2)
    prog = correctness.check(cell, fin, 2)["max_logit_gap"]
    ctrl = correctness.check(cell, fin, 2, quant="fp8")["max_logit_gap"]
    assert prog["value"] <= prog["limit"] < ctrl["value"], (prog, ctrl)


@pytest.fixture
def fresh_programs():
    engine_mod._JIT_CACHE.clear()
    aot.clear_cache()
    yield
    engine_mod._JIT_CACHE.clear()
    aot.clear_cache()


def test_a_token_altered_in_the_tick_is_not_correct(smoke_root, monkeypatch,
                                                     fresh_programs):
    orig = engine_mod.make_engine_tick

    def broken(cfg, steps):
        tick = orig(cfg, steps)

        def altered(params, tok, pos, live, caches, cross=None,
                    library=None):
            toks, tok, pos, ok, caches = tick(params, tok, pos, live, caches,
                                              cross, library)
            return (toks + 1) % cfg.vocab_size, tok, pos, ok, caches

        return altered

    monkeypatch.setattr(engine_mod, "make_engine_tick", broken)
    r = harness.run(CELL, 2, 1.5, False, time.perf_counter(), smoke_root)
    assert r["correct"] is False
    assert r["compared"]["max_logit_gap"]["value"] > \
        r["compared"]["max_logit_gap"]["limit"]


def test_the_unbroken_run_is_correct(smoke_root, fresh_programs):
    r = harness.run(CELL, 2, 1.5, False, time.perf_counter(), smoke_root)
    assert r["correct"] is True, r["compared"]
