"""The per-layer metrics that read the engine's own stamps and spans:
``prefill_p95_ms``, ``first_token_hold_p95_ms``, ``aot_warmup_s`` on
synthetic runs and on a smoke cell driven on the CPU, ``host_idle_share`` on
a trace recorded on a TPU v5e with the engine spans in it, and their reading
nothing (None, no error) from a program that records none of it.

``data/v5e_engine.xplane.pb``: a 2-layer Yi-6B-width engine with
interp-fused numerics, 4 slots, bucket 128, horizon 2; inside a
``traced_window`` span, two harness ``step`` spans: one packed admission of
two requests and a 2-step tick, then a 2-step tick. The ``/host:metadata``
plane (the programs' HLO) was dropped to keep the file small."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import types

import numpy as np
import pytest

from bench import engine_spans, harness, trace, traffic
from bench.harness import Rec, module
from repro.serve import spans as span_lib

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "v5e_engine.xplane.pb"
NEW = {"prefill_p95_ms": ("ms", "engine admission", "ttft_p95_ms"),
       "first_token_hold_p95_ms": ("ms", "engine decode tick",
                                   "ttft_p95_ms"),
       "aot_warmup_s": ("s", "engine set-up", "setup_s"),
       "host_idle_share": ("%", "device / engine host",
                           "output_tokens_per_s")}


def _req(**stamps):
    keys = ("submitted_at", "admitted_at", "first_token_at",
            "first_token_returned_at", "finished_at")
    return types.SimpleNamespace(**{k: stamps.get(k) for k in keys})


def _run(recs, seconds=10.0, **kw):
    return types.SimpleNamespace(recs=recs, seconds=seconds, trace=None,
                                 **kw)


def _rec(due, req):
    return Rec(0, due, np.zeros(4, np.int32), 8, req=req)


def test_prefill_and_hold_read_the_engine_stamps_of_requests_due():
    recs = [_rec(1.0 + i, _req(admitted_at=100.0 + i,
                               first_token_at=100.2 + i + 0.01 * i,
                               first_token_returned_at=101.1 + i + 0.01 * i))
            for i in range(5)]
    recs += [_rec(-1.0, _req(admitted_at=0.0, first_token_at=9.0,
                             first_token_returned_at=99.0)),  # lead-in
             _rec(20.0, _req(admitted_at=0.0, first_token_at=9.0)),  # late
             _rec(3.5, _req(admitted_at=5.0)),  # no first token yet
             _rec(4.5, None)]  # never submitted
    run = _run(recs)
    assert engine_spans.gaps_s(run, "admitted_at", "first_token_at") == \
        pytest.approx([0.2, 0.21, 0.22, 0.23, 0.24])
    pre = module("metrics", "prefill_p95_ms").read(run)
    assert pre == pytest.approx(1e3 * np.percentile(
        [0.2, 0.21, 0.22, 0.23, 0.24], 95))
    hold = module("metrics", "first_token_hold_p95_ms").read(run)
    assert hold == pytest.approx(900.0)


def test_aot_warmup_is_the_aot_span_of_the_newest_engine_init(monkeypatch):
    rec = span_lib.SpanRecorder(iter(range(100)).__next__)
    monkeypatch.setattr(span_lib, "_default", rec)
    read = module("metrics", "aot_warmup_s").read
    assert read(_run([])) is None  # no engine yet
    with rec.span("engine.init"):
        with rec.span("engine.aot"):  # clock reads 1 ...
            with rec.span("engine.aot.program", key="tick/1"):
                with rec.span("lower"):
                    pass
                with rec.span("compile"):
                    pass
        # ... 8
        with rec.span("engine.verify_rom"):
            pass
    assert read(_run([])) == 7.0
    # a newer engine without AOT warm-up: nothing to read
    with rec.span("engine.init"):
        pass
    assert read(_run([])) is None


def test_readers_read_nothing_from_a_program_without_stamps_or_spans(
        monkeypatch):
    """What the benchmark reads of a checkout whose engine predates the
    stamps and spans: None, never an error."""
    plain = types.SimpleNamespace(out=[1, 2])  # a Request without stamps
    run = _run([_rec(1.0, plain), _rec(2.0, plain)])
    assert module("metrics", "prefill_p95_ms").read(run) is None
    assert module("metrics", "first_token_hold_p95_ms").read(run) is None
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert engine_spans.aot_span() is None
    assert module("metrics", "aot_warmup_s").read(run) is None
    assert module("metrics", "host_idle_share").read(run) is None


def test_interval_arithmetic():
    u = engine_spans.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 0, 25)
    assert u == [(0, 3), (5, 12), (20, 25)]
    assert engine_spans.minus(u, [(1, 2), (4, 6), (11, 21)]) == \
        [(0, 1), (2, 3), (6, 11), (21, 25)]
    assert engine_spans.minus([(0, 10)], []) == [(0, 10)]
    assert engine_spans.minus([(0, 10)], [(0, 10)]) == []
    assert engine_spans.length([(0, 1), (2, 5)]) == 4


def test_host_idle_ns_on_a_synthetic_trace():
    dev = {"ops": [[0, 10, "a", None], [5, 10, "b", None],
                   [30, 10, "c", None]]}  # busy [0, 15) and [30, 40)
    host = {engine_spans.STEP: [(10, 50)],
            engine_spans.SYNC: [(12, 20), (32, 45)]}
    # idle [15, 30) and [40, 50); in step, not in sync: [10, 12), [20, 32),
    # [45, 50): both in [20, 30) and [45, 50)
    assert engine_spans.host_idle_ns(dev, host, 0, 50) == 15
    assert engine_spans.host_idle_ns(dev, host, 0, 48) == 13


# -- the recorded trace --------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A checkout-like directory whose ``artifacts/bench_trace`` holds the
    recorded trace, as a ``--trace 1`` run leaves it."""
    root = tmp_path_factory.mktemp("traced")
    d = root / "artifacts" / "bench_trace" / "plugins" / "profile" / "0"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / "host.xplane.pb")
    return root, trace.load(str(TRACE))


def test_recorded_trace_holds_the_engine_spans_inside_the_harness_step(
        recorded):
    root, tr = recorded
    host = engine_spans.host_spans(
        trace.find_xplane(str(root / "artifacts" / "bench_trace")),
        names=("step", "engine.step", "engine.admit", "engine.prefill",
               "engine.tick", "engine.sync", "engine.retire"))
    assert len(host["step"]) == len(host["engine.step"]) == 2
    for s, e in host["engine.step"]:
        assert any(hs <= s and e <= he for hs, he in host["step"])
    for name in ("engine.admit", "engine.tick", "engine.retire"):
        for s, e in host[name]:
            assert any(hs <= s and e <= he for hs, he in host["engine.step"])
    (ps, pe), = host["engine.prefill"]  # one packed admission
    assert any(hs <= ps and pe <= he for hs, he in host["engine.admit"])
    # every wait for the device is inside an admission or a tick
    outer = host["engine.prefill"] + host["engine.tick"]
    assert len(host["engine.sync"]) == 3
    for s, e in host["engine.sync"]:
        assert any(hs <= s and e <= he for hs, he in outer)


def test_recorded_kernels_carry_their_names(recorded):
    _, tr = recorded
    kernels = {o[2] for o in tr["devices"][0]["ops"] if o[3] is not None}
    assert {"flash_lib", "rmsnorm_lib", "_library_eval"} <= kernels
    assert not any(k.startswith("closed_call") for k in kernels)
    d = tr["devices"][0]
    flash = module("metrics", "flash_lib_roofline").is_flash
    act = module("metrics", "act_lib_roofline").is_act
    assert all(o[2] == "flash_lib" for o in d["ops"] if flash(o[2], o[3]))
    assert any(flash(o[2], o[3]) for o in d["ops"])
    assert any(act(o[2], o[3]) for o in d["ops"])


def test_host_idle_share_on_the_recorded_trace(recorded):
    root, tr = recorded
    cell = types.SimpleNamespace(bench_dir=root / "bench", chips=1)
    run = types.SimpleNamespace(trace=tr, cell=cell)
    got = module("metrics", "host_idle_share").read(run)
    # a part of the device's idle time
    assert 0.0 < got <= module("metrics", "device_idle_share").read(run)
    lo, hi = trace.window(tr)
    # the same share counted on a 1 us grid
    host = engine_spans.host_spans(str(TRACE))
    grid = np.arange(lo, hi, 1000.0)
    busy = np.zeros(grid.size, bool)
    for s, dur, *_ in tr["devices"][0]["ops"]:
        busy |= (grid >= s) & (grid < s + dur)
    step = np.zeros(grid.size, bool)
    for s, e in host[engine_spans.STEP]:
        step |= (grid >= s) & (grid < e)
    for s, e in host[engine_spans.SYNC]:
        step &= ~((grid >= s) & (grid < e))
    want = 100.0 * np.mean(~busy & step)
    assert got == pytest.approx(want, abs=0.05)


# -- the benchmark's entries and a smoke cell -----------------------------------

def test_the_four_entries_reach_the_smoke_checkout(smoke_root):
    bench = json.loads((smoke_root / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cell = harness.load_cell("yi_smoke.interp.tiny", smoke_root)
    for name, (unit, layer, moves) in NEW.items():
        m = per_layer[name]
        assert (m["unit"], m["layer"], m["moves"]) == (unit, layer, moves)
        assert m["source"] in ("host_clock", "device_trace")
        assert callable(module("metrics", name, cell.bench_dir).read)
        assert name in [x["name"] for x in cell.per_layer]


def test_smoke_cell_stamps_and_live_slot_counter(smoke_root):
    """A smoke cell through the harness's open loop: the stamp readers read
    a real engine's requests, and the engine's live-slot counter gives the
    harness's decode occupancy."""
    cell = harness.load_cell("yi_smoke.exact.tiny", smoke_root)
    seed = 2**33 + 5
    eng = harness.build_engine(cell, harness.make_weights(cell, seed),
                               smoke_root / "artifacts" / "bench_tables")
    harness.warm_up(eng, cell, cell.hf["vocab_size"], seed)
    sched = traffic.arrivals(cell.mix, cell.params["rate"], 1.5, seed,
                             cell.hf["vocab_size"])
    s0 = dict(eng.stats)
    recs, steps, _, tainted = harness.drive(eng, sched, 1.5)
    assert tainted is None
    live = eng.stats["decode_live_slot_steps"] - s0["decode_live_slot_steps"]
    dec = eng.stats["decode_steps"] - s0["decode_steps"]
    # decode_occupancy's arithmetic over every step the loop took
    tokens = sum(n for s in steps for _, n in s["live"])
    assert dec == sum(s["steps"] for s in steps) > 0
    assert live == tokens
    assert live / dec == pytest.approx(tokens / sum(s["steps"]
                                                    for s in steps))
    run = harness.Run(cell, 1.5, recs, steps, 0.0, {}, None)
    assert module("metrics", "decode_occupancy", cell.bench_dir).read(run) \
        <= cell.config["serve"]["slots"]
    pre = module("metrics", "prefill_p95_ms", cell.bench_dir).read(run)
    hold = module("metrics", "first_token_hold_p95_ms",
                  cell.bench_dir).read(run)
    assert pre is not None and pre > 0
    assert hold is not None and hold > 0
    aot = module("metrics", "aot_warmup_s", cell.bench_dir).read(run)
    assert aot is not None and aot > 0
    for r in recs:
        if r.req is not None and r.req.first_token_at is not None:
            assert (r.req.submitted_at <= r.req.admitted_at
                    <= r.req.first_token_at
                    <= r.req.first_token_returned_at)
