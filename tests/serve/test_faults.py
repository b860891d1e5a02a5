"""Chaos suite: the serving-robustness layer under injected faults
(DESIGN.md §14).

Every fault is deterministic (seeded injectors from :mod:`repro.faults`),
so each scenario is a reproducible experiment with an exact expected
outcome:

  * admission control — bounded-queue backpressure, out-of-vocab prompt
    rejection, and the regression for unbounded queue growth under
    sustained over-admission;
  * deadlines — queued and in-flight expiry against an injectable clock;
  * the tick watchdog — NaN'd, dropped and stalled ticks retire poisoned
    slots with structured errors and walk the degradation ladder;
  * ROM integrity — a seeded single-bit flip of the resident coefficient
    ROM is caught by ``verify_resident()`` and degrades the engine to
    exact numerics, whose tokens must be identical to an uncorrupted
    exact-numerics run (the ISSUE-7 acceptance oracle).
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.api import LibraryIntegrityError, default_explorer
from repro.configs.base import get_smoke_config
from repro.faults import (FaultClock, TickFaultInjector, flip_rom_bit,
                          poison_prompt, reset_crashpoints)
from repro.models import transformer as tf
from repro.serve.engine import Rejected, Request, ServeEngine

MAX_NEW = 5


@pytest.fixture(autouse=True)
def _clean_crashpoints():
    reset_crashpoints()
    yield
    reset_crashpoints()


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


# ------------------------------------------------------------ admission

def test_queue_full_rejection(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=32, max_queue=2)
    for i, p in enumerate(_prompts(cfg, (4, 4))):
        eng.submit(Request(i, p, max_new=2))
    with pytest.raises(Rejected, match="queue full") as ei:
        eng.submit(Request(2, _prompts(cfg, (4,))[0], max_new=2))
    assert ei.value.reason == "queue_full"
    assert isinstance(ei.value, ValueError)  # pre-ISSUE-7 callers survive
    assert eng.stats["rejected"] == 1


def test_queue_stays_bounded_under_sustained_over_admission(model):
    """Regression (ISSUE 7 satellite): with backpressure on, sustained
    over-admission cannot grow the queue past ``max_queue`` — every
    overflow is a typed rejection, not silent unbounded growth."""
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=32, max_queue=3)
    prompt = _prompts(cfg, (4,))[0]
    rejected = 0
    for i in range(50):
        try:
            eng.submit(Request(i, prompt, max_new=2))
        except Rejected as e:
            assert e.reason == "queue_full"
            rejected += 1
        assert len(eng.queue) <= 3
    assert rejected == 50 - 3
    assert eng.stats["rejected"] == rejected
    # the engine still drains the admitted work
    done = eng.run()
    assert len(done) == 3


def test_poisoned_prompt_rejected(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=32)
    bad = poison_prompt(_prompts(cfg, (6,))[0], cfg.vocab_size, seed=3)
    with pytest.raises(Rejected, match="outside vocab") as ei:
        eng.submit(Request(0, bad, max_new=2))
    assert ei.value.reason == "bad_prompt"
    with pytest.raises(Rejected):
        eng.submit(Request(1, np.zeros(0, np.int32), max_new=2))


def test_overflow_rejections_are_typed(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=16)
    with pytest.raises(Rejected) as ei:
        eng.submit(Request(0, np.zeros(17, np.int32), max_new=1))
    assert ei.value.reason == "prompt_overflow"
    with pytest.raises(Rejected) as ei:
        eng.submit(Request(1, np.zeros(12, np.int32), max_new=8))
    assert ei.value.reason == "decode_overflow"


# ------------------------------------------------------------- deadlines

def test_deadline_expires_queued_request(model):
    cfg, params = model
    clk = FaultClock()
    eng = ServeEngine(cfg, params, slots=1, cache_len=32, clock=clk,
                      deadline_s=10.0)
    p0, p1 = _prompts(cfg, (4, 4))
    eng.submit(Request(0, p0, max_new=2))
    eng.submit(Request(1, p1, max_new=2))  # waits behind request 0
    clk.advance(11.0)  # both deadlines pass before any decode
    eng.run()
    # queued work past its deadline fails structurally, never decodes
    assert all(r.error == "deadline_exceeded" for r in eng.failed)
    assert eng.stats["expired"] == len(eng.failed) > 0


def test_deadline_expires_in_flight_request(model):
    cfg, params = model
    clk = FaultClock()
    eng = ServeEngine(cfg, params, slots=1, cache_len=64, clock=clk)
    (p,) = _prompts(cfg, (4,))
    eng.submit(Request(0, p, max_new=30, deadline=5.0))
    eng.step()  # admitted and decoding
    assert eng.req[0] is not None
    clk.advance(6.0)
    eng.step()
    assert eng.req[0] is None  # slot freed
    (failed,) = eng.failed
    assert failed.error == "deadline_exceeded"
    assert eng.stats["expired"] == 1


def test_submit_past_deadline_rejected(model):
    cfg, params = model
    clk = FaultClock(start=100.0)
    eng = ServeEngine(cfg, params, slots=1, cache_len=32, clock=clk)
    with pytest.raises(Rejected) as ei:
        eng.submit(Request(0, _prompts(cfg, (4,))[0], max_new=2,
                           deadline=99.0))
    assert ei.value.reason == "deadline"


# ---------------------------------------------------------- tick watchdog

def test_nan_tick_retires_slot_with_structured_error(model):
    """A poisoned fused tick (sentinel tripped) must retire the slot with a
    structured error — its garbage chunk is never appended to the stream —
    and count a watchdog trip."""
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=2, cache_len=48, fused=True,
                      watchdog_limit=100)  # don't degrade in this test
    inj = TickFaultInjector("nan", every_n=1, limit=1).install(eng)
    for i, p in enumerate(_prompts(cfg, (5, 7))):
        eng.submit(Request(i, p, max_new=MAX_NEW))
    eng.run()
    assert inj.injected == 1
    assert eng.stats["watchdog_trips"] == 1
    assert len(eng.failed) == 2  # both live slots were in the poisoned tick
    for r in eng.failed:
        assert r.error == "non_finite_output"
        assert len(r.out) == 1  # only the admission token, no garbage chunk


def test_repeated_nan_ticks_degrade_fused_to_serial(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=64, fused=True,
                      watchdog_limit=2)
    TickFaultInjector("nan", every_n=1, limit=2).install(eng)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 4).astype(
            np.int32), max_new=3))
    eng.run()
    assert eng.stats["watchdog_trips"] == 2
    assert eng.stats["degradations"] == 1
    assert eng.fused is False  # fused -> serial rung
    assert any(f["action"] == "fused->serial" for f in eng.faults)
    # post-degradation the engine still completes the remaining requests
    assert len(eng.finished) == 2
    assert all(len(r.out) == 3 for r in eng.finished)


def test_degraded_interp_engine_uses_guarded_numerics():
    cfg = get_smoke_config("yi_6b").replace(numerics="interp")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, cache_len=48, fused=True,
                      watchdog_limit=1)
    TickFaultInjector("nan", every_n=1, limit=1).install(eng)
    rng = np.random.default_rng(1)
    for i in range(2):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 4).astype(
            np.int32), max_new=3))
    eng.run()
    # the serial rung of an interp engine serves through the domain guard
    assert eng.cfg.numerics == "interp-guarded"
    assert eng.numerics.__class__.__name__ == "GuardedNumerics"
    assert len(eng.finished) == 1


def test_dropped_tick_makes_no_silent_progress(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, slots=1, cache_len=48, fused=True,
                      watchdog_limit=100)
    inj = TickFaultInjector("drop", every_n=1, limit=1).install(eng)
    (p,) = _prompts(cfg, (5,))
    eng.submit(Request(0, p, max_new=MAX_NEW))
    eng.run()
    assert inj.injected == 1
    # the dropped tick's zero tokens were never streamed as real output
    (failed,) = eng.failed
    assert failed.error == "non_finite_output"
    assert len(failed.out) == 1


def test_stalled_tick_trips_watchdog(model):
    cfg, params = model
    clk = FaultClock()
    eng = ServeEngine(cfg, params, slots=1, cache_len=48, fused=True,
                      clock=clk, max_tick_s=0.5, watchdog_limit=100)
    TickFaultInjector("delay", every_n=1, delay_s=2.0, limit=1).install(eng)
    (p,) = _prompts(cfg, (5,))
    eng.submit(Request(0, p, max_new=MAX_NEW))
    eng.run()
    assert eng.stats["watchdog_trips"] == 1
    assert any(f["reason"] == "stalled_tick" for f in eng.faults)
    # a stall poisons no data: the request still completed
    (done,) = eng.finished
    assert len(done.out) == MAX_NEW


# ------------------------------------------------------------ ROM integrity

def test_flipped_rom_bit_detected_by_verify_resident():
    lib = default_explorer().compile()
    lib.verify_resident()  # healthy baseline passes
    flipped = flip_rom_bit(lib, seed=11)
    with pytest.raises(LibraryIntegrityError, match="checksum"):
        flipped.verify_resident()
    # a different seed flips a different bit; still caught
    with pytest.raises(LibraryIntegrityError):
        flip_rom_bit(lib, seed=12).verify_resident()


def test_corrupt_rom_degrades_to_exact_with_identical_tokens():
    """The ISSUE-7 acceptance oracle: an engine handed a silently corrupted
    library detects it at construction, degrades straight to exact
    numerics, and its token streams are bitwise identical to an engine
    built with exact numerics and no library."""
    cfg = get_smoke_config("yi_6b").replace(numerics="interp")
    params = tf.init_params(jax.random.key(0), cfg)
    flipped = flip_rom_bit(default_explorer().compile(), seed=5)
    eng = ServeEngine(cfg, params, slots=2, cache_len=48, fused=True,
                      library=flipped)
    assert eng.stats["rom_faults"] == 1
    assert eng.cfg.numerics == "exact" and eng.library is None
    assert any(f["reason"] == "rom_integrity" for f in eng.faults)

    ref = ServeEngine(get_smoke_config("yi_6b"), params, slots=2,
                      cache_len=48, fused=True)
    prompts = _prompts(get_smoke_config("yi_6b"), (5, 11, 3))
    for e in (eng, ref):
        for i, p in enumerate(prompts):
            e.submit(Request(i, p, max_new=MAX_NEW))
    got = {r.rid: r.out for r in eng.run()}
    want = {r.rid: r.out for r in ref.run()}
    assert got == want


def test_periodic_rom_verify_catches_runtime_corruption():
    cfg = get_smoke_config("yi_6b").replace(numerics="interp")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, cache_len=64, fused=True,
                      verify_rom_every=1)
    rng = np.random.default_rng(2)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       max_new=12))
    eng.step(2)
    # the resident ROM goes bad mid-serve
    eng.library = flip_rom_bit(eng.library, seed=9)
    eng.step(2)
    assert eng.stats["rom_faults"] == 1
    assert eng.cfg.numerics == "exact" and eng.library is None
    eng.run()  # finishes on the exact rung
    (done,) = eng.finished
    assert len(done.out) == 12


def test_serve_cli_exit_status_reflects_degradation(tmp_path):
    """``repro.launch.serve`` exits 1 when the run degraded, faulted or
    failed a request (``degraded`` names why), 0 for a clean run."""
    import subprocess
    import sys
    from types import SimpleNamespace

    from repro.launch.serve import degraded

    clean = SimpleNamespace(stats={"degradations": 0}, faults=[], failed=[])
    assert degraded(clean) == []
    assert degraded(SimpleNamespace(stats={"degradations": {"0": 1}},
                                    faults=[], failed=[]))
    assert degraded(SimpleNamespace(stats={"degradations": 0},
                                    faults=[{"reason": "x"}], failed=[]))
    assert degraded(SimpleNamespace(stats={"degradations": 0}, faults=[],
                                    failed=[object()]))
    import os
    import pathlib

    src = pathlib.Path(__file__).resolve().parents[2] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "yi_6b",
         "--smoke", "--requests", "2", "--slots", "2", "--prompt-len", "4",
         "--max-new", "3", "--cache-len", "16"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "served 2 requests" in out.stdout
