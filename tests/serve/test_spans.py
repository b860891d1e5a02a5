"""Engine spans and request stamps (``repro.serve.spans``): the recorder's
nesting and bounded ring, the span tree a tiny engine records on an
injected clock, the per-request stamps, the AOT construction spans, and the
live-slot counter."""
from __future__ import annotations

import itertools

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.serve import spans as span_lib
from repro.serve.aot import BucketTable, compile_count
from repro.serve.engine import Request, ServeEngine

CACHE = 48


class TickClock:
    """Each read is one later than the last: every stamp and span edge is
    distinct and ordered as it was taken."""

    def __init__(self):
        self._n = itertools.count()

    def __call__(self) -> float:
        return float(next(self._n))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _served(setup, lens=(5, 7, 12, 6), max_new=4, **kw):
    cfg, params = setup
    kw = {"slots": 2, "aot_buckets": (8, 16), "clock": TickClock(), **kw}
    eng = ServeEngine(cfg, params, cache_len=CACHE, **kw)
    reqs = [Request(i, p, max_new=max_new)
            for i, p in enumerate(_prompts(cfg, lens))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


# -- the recorder ------------------------------------------------------------

def test_spans_nest_and_record_parent_ids():
    rec = span_lib.SpanRecorder(TickClock())
    with rec.span("a") as a:
        with rec.span("b", k=1) as b:
            pass
        with rec.span("c"):
            with rec.span("d"):
                pass
    got = {s.name: s for s in rec.spans()}
    assert [s.name for s in rec.spans()] == ["b", "d", "c", "a"]
    assert got["a"].parent is None
    assert got["b"].parent == got["c"].parent == got["a"].id
    assert got["d"].parent == got["c"].id
    assert got["b"].attrs == {"k": 1}
    assert a.span is got["a"] and b.span is got["b"]
    # a child lies inside its parent
    assert got["a"].t0 < got["b"].t0 < got["b"].t1 < got["a"].t1


def test_span_is_recorded_when_its_block_raises():
    rec = span_lib.SpanRecorder(TickClock())
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError("x")
    assert [s.name for s in rec.spans()] == ["inner", "outer"]
    with rec.span("next") as nxt:
        pass
    assert nxt.span.parent is None  # nothing was left open


def test_ring_stays_bounded_and_last_outlives_it():
    rec = span_lib.SpanRecorder(TickClock())
    with rec.span("engine.init"):
        pass
    for _ in range(span_lib.CAPACITY):
        with rec.span("engine.step"):
            with rec.span("engine.tick"):
                pass
    assert len(rec.spans()) == span_lib.CAPACITY
    assert all(s.name != "engine.init" for s in rec.spans())
    assert rec.last("engine.init").name == "engine.init"
    assert rec.last("engine.step") == rec.spans()[-1]
    assert rec.last("nothing") is None


def test_attrs_reach_the_profiler_annotation_unchanged_in_the_ring():
    rec = span_lib.SpanRecorder(TickClock())
    with rec.span("engine.prefill", bucket=8, pack=2, rids=(3, 4)):
        pass
    assert rec.spans()[0].attrs == {"bucket": 8, "pack": 2, "rids": (3, 4)}
    # the annotation's encoding ("name#k=v,k=v#") cannot hold a comma
    assert span_lib._annotation_value((3, 4)) == "3 4"


# -- the engine's spans --------------------------------------------------------

@pytest.mark.parametrize("fused", [True, False], ids=["fused", "serial"])
def test_engine_span_tree(setup, fused):
    kw = {} if fused else {"fused": False, "aot_buckets": None}
    eng, reqs = _served(setup, **kw)
    ring = eng.spans()
    by_id = {s.id: s for s in ring}
    steps = [s for s in ring if s.name == "engine.step"]
    assert steps and all(s.parent is None for s in steps)
    step_ids = {s.id for s in steps}

    def parent(s):
        return by_id[s.parent].name

    for s in ring:
        if s.name in ("engine.admit", "engine.tick", "engine.retire"):
            assert s.parent in step_ids, s
        elif s.name == "engine.prefill":
            assert parent(s) == "engine.admit"
        elif s.name == "engine.sync":
            assert parent(s) in ("engine.prefill", "engine.tick")
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.t0 < s.t0 <= s.t1 < p.t1
    names = {s.name for s in ring}
    assert {"engine.step", "engine.admit", "engine.prefill", "engine.tick",
            "engine.sync", "engine.retire"} <= names
    ticks = [s for s in ring if s.name == "engine.tick"]
    assert sum(t.attrs["steps"] for t in ticks) == eng.stats["decode_steps"]
    assert all(1 <= t.attrs["live"] <= eng.slots for t in ticks)
    # every admission waited for its first tokens, every tick for its block
    syncs = [s for s in ring if s.name == "engine.sync"]
    assert {by_id[s.parent].name for s in syncs} == {"engine.prefill",
                                                     "engine.tick"}


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "serial"])
def test_prefill_spans_carry_their_rids(setup, fused):
    kw = {} if fused else {"fused": False, "aot_buckets": None}
    eng, reqs = _served(setup, **kw)
    pre = [s for s in eng.spans() if s.name == "engine.prefill"]
    rids = [rid for s in pre for rid in s.attrs["rids"]]
    assert sorted(rids) == [r.rid for r in reqs]  # each admitted once
    assert all(s.attrs["pack"] == len(s.attrs["rids"]) for s in pre)
    if fused:
        assert {s.attrs["bucket"] for s in pre} <= {8, 16}
        assert eng.stats["packed_admits"] == len(pre)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "serial"])
def test_request_stamps_are_ordered(setup, fused):
    kw = {} if fused else {"fused": False, "aot_buckets": None}
    eng, reqs = _served(setup, max_new=1 if not fused else 4, **kw)
    for r in reqs:
        t = (r.submitted_at, r.admitted_at, r.first_token_at,
             r.first_token_returned_at, r.finished_at)
        assert None not in t, (r.rid, t)
        assert list(t) == sorted(t), (r.rid, t)
        assert r.submitted_at < r.admitted_at < r.first_token_at
    # the first token is returned with the end of the step that made it
    ends = {s.t1 for s in eng.spans() if s.name == "engine.step"}
    assert all(r.first_token_returned_at in ends for r in reqs)
    assert all(r.finished_at in ends for r in reqs)


def test_request_stamps_with_the_async_pipeline(setup):
    eng, reqs = _served(setup, async_host=True)
    eng.close()
    for r in reqs:
        assert None not in (r.submitted_at, r.admitted_at, r.first_token_at,
                            r.first_token_returned_at, r.finished_at)
        # the worker downloads the first token after the dispatch
        assert r.admitted_at < r.first_token_at
        assert r.submitted_at <= r.admitted_at <= r.first_token_returned_at


def test_one_aot_program_span_per_warmed_program(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=4, cache_len=CACHE,
                      aot_buckets=(8, 16, 32), max_pack=2, horizon=4,
                      clock=TickClock())
    ring = eng.spans()
    init = span_lib.default().last("engine.init")
    assert init is not None and init.parent is None
    assert init.id == next(s.id for s in ring if s.name == "engine.init")
    aot = [s for s in ring if s.name == "engine.aot"]
    assert len(aot) == 1 and aot[0].parent == init.id
    verify = [s for s in ring if s.name == "engine.verify_rom"]
    assert len(verify) == (0 if eng.library is None else 1)
    progs = [s for s in ring if s.name == "engine.aot.program"]
    assert all(p.parent == aot[0].id for p in progs)
    assert len(progs) == compile_count(BucketTable((8, 16, 32)), 2, 4, 4)
    keys = [p.attrs["key"] for p in progs]
    assert len(set(keys)) == len(keys)
    assert "tick/4" in keys and "admit_packed/32/2" in keys
    # a fresh program lowers then compiles; a cached one does neither
    fresh = 0
    for p in progs:
        kids = [s.name for s in ring if s.parent == p.id]
        assert kids in ([], ["lower", "compile"])
        fresh += bool(kids)
    assert fresh == eng.stats["aot_compiles"]


def test_newest_engine_is_the_process_default(setup):
    cfg, params = setup
    a = ServeEngine(cfg, params, slots=2, cache_len=CACHE)
    assert span_lib.default().spans() == a.spans()
    b = ServeEngine(cfg, params, slots=2, cache_len=CACHE)
    assert span_lib.default().spans() == b.spans()
    assert span_lib.default().last("engine.init") == b.spans()[-1]


def test_verify_rom_span_when_rechecked_every_tick(setup):
    cfg, params = setup
    icfg = cfg.replace(numerics="interp-fused")
    eng = ServeEngine(icfg, params, slots=2, cache_len=CACHE,
                      verify_rom_every=1, clock=TickClock())
    eng.submit(Request(0, _prompts(cfg, [5])[0], max_new=3))
    eng.run()
    ring = eng.spans()
    by_id = {s.id: s for s in ring}
    checks = [s for s in ring if s.name == "engine.verify_rom"]
    assert by_id[checks[0].parent].name == "engine.init"
    assert {by_id[s.parent].name for s in checks[1:]} == {"engine.step"}
    assert len(checks) == eng.stats["rom_verifies"]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "serial"])
def test_live_slot_steps_counts_live_slots_per_decode_step(setup, fused):
    kw = {} if fused else {"fused": False, "aot_buckets": None}
    eng, _ = _served(setup, lens=(5, 7, 12), max_new=6, **kw)
    ticks = [s for s in eng.spans() if s.name == "engine.tick"]
    assert eng.stats["decode_live_slot_steps"] == sum(
        t.attrs["live"] * t.attrs["steps"] for t in ticks)
    assert 0 < eng.stats["decode_live_slot_steps"] \
        <= eng.slots * eng.stats["decode_steps"]
