"""The fused serve tick (ISSUE 5): one donated-buffer dispatch per chunk of
decode steps, greedy argmax inside the program, library-bound fused kernels
for interp numerics.

Oracles: (1) the fused engine against the serial per-op path — bitwise
token equality with exact numerics (same decode program, only the dispatch
granularity changes); (2) mixed-length continuous batching through the
fused engine against the PR-4 one-request-at-a-time oracle, interp
numerics end to end; (3) buffer identity across ticks — donation means the
KV-cache pool is updated in place, not copied.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine

MAX_NEW = 6


def _mk(cfg, params, *, fused, slots=2, cache_len=48, horizon=8, lib=None):
    return ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                       library=lib, fused=fused, horizon=horizon)


def _prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def test_fused_tokens_bitwise_equal_serial_exact_numerics():
    """Exact numerics: the fused tick runs the same decode program as the
    serial path (scan granularity only) — token streams are identical."""
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    outs = {}
    for fused in (False, True):
        eng = _mk(cfg, params, fused=fused)
        for i, p in enumerate(_prompts(cfg, (5, 11, 3))):
            eng.submit(Request(i, p, max_new=MAX_NEW))
        outs[fused] = {r.rid: r.out for r in eng.run()}
    assert outs[True] == outs[False]


@pytest.mark.parametrize("arch", ["yi_6b", "minicpm3_4b"])
def test_fused_mixed_length_batching_matches_solo_oracle(arch):
    """The PR-4 oracle through the fused engine with interp numerics: the
    full fused datapath (library kernels + chunked tick) must make batching
    invisible — every request decodes exactly as if served alone."""
    cfg = get_smoke_config(arch).replace(numerics="interp")
    params = tf.init_params(jax.random.key(0), cfg)
    prompts = _prompts(cfg, (5, 11, 3))
    eng = _mk(cfg, params, fused=True)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=MAX_NEW))
    done = {r.rid: r.out for r in eng.run()}
    assert set(done) == {0, 1, 2}
    for i, p in enumerate(prompts):
        solo = _mk(cfg, params, fused=True, slots=1)
        solo.submit(Request(i, p, max_new=MAX_NEW))
        (ref,) = solo.run()
        assert done[i] == ref.out, f"request {i} (len {len(p)}) diverged"


def test_fused_horizon_chunking_is_invisible():
    """Tokens are independent of the chunk size (horizon 1 vs 8) and of
    stepping manually one decode at a time."""
    cfg = get_smoke_config("yi_6b").replace(numerics="interp")
    params = tf.init_params(jax.random.key(0), cfg)
    prompts = _prompts(cfg, (4, 9))
    outs = []
    for horizon in (1, 3, 8):
        eng = _mk(cfg, params, fused=True, horizon=horizon)
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, max_new=MAX_NEW))
        outs.append({r.rid: r.out for r in eng.run()})
    assert outs[0] == outs[1] == outs[2]


def test_fused_tick_donates_cache_buffers():
    """Donation contract (satellite): across ticks the KV-cache pool leaves
    are updated in place — the output arrays reuse the input buffers, so a
    decode tick never copies the pool."""
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = _mk(cfg, params, fused=True, slots=2, cache_len=64)
    eng.submit(Request(0, _prompts(cfg, (5,))[0], max_new=24))
    eng.step(4)  # admission + first chunk (fresh buffers land here)
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(eng.caches)]
    eng.step(4)
    after = [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(eng.caches)]
    assert ptrs == after, "cache pool was copied despite donation"
    # slot-state buffers (token / position vectors) are donated too
    tok_ptr = eng._tok_dev.unsafe_buffer_pointer()
    pos_ptr = eng._pos_dev.unsafe_buffer_pointer()
    eng.step(4)
    assert eng._tok_dev.unsafe_buffer_pointer() == tok_ptr
    assert eng._pos_dev.unsafe_buffer_pointer() == pos_ptr


def test_fused_dispatch_counts_collapse():
    """The serve-tick contract: the serial path pays >= 2 program dispatches
    per decoded token; the fused path amortizes 1 dispatch + 1 transfer
    over the whole chunk."""
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    stats = {}
    for fused in (False, True):
        eng = _mk(cfg, params, fused=fused, slots=2, horizon=8)
        for i, p in enumerate(_prompts(cfg, (5, 9))):
            eng.submit(Request(i, p, max_new=9))
        eng.run()
        stats[fused] = dict(eng.stats)
    serial, fused_s = stats[False], stats[True]
    assert serial["dispatches"] == 2 * serial["decode_steps"]
    assert fused_s["dispatches"] == fused_s["ticks"]
    assert fused_s["decode_steps"] > 2 * fused_s["ticks"]  # real amortization
    assert fused_s["dispatches"] < serial["dispatches"] / 4


def test_interp_fused_backend_name_serves():
    """The explicit "interp-fused" cfg backend name drives the engine like
    "interp": library auto-compiled, admission/tick usable."""
    cfg = get_smoke_config("yi_6b").replace(numerics="interp-fused")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = _mk(cfg, params, fused=True)
    eng.submit(Request(0, _prompts(cfg, (5,))[0], max_new=4))
    (done,) = eng.run()
    assert len(done.out) >= 4
    # and it decodes identically to numerics="interp" on a fused engine
    eng2 = _mk(get_smoke_config("yi_6b").replace(numerics="interp"), params,
               fused=True)
    eng2.submit(Request(0, _prompts(cfg, (5,))[0], max_new=4))
    (ref,) = eng2.run()
    assert done.out == ref.out


def test_fused_engine_windowed_wrap():
    """Sliding-window engine through the fused tick: long prompt, wrapped
    decode — equality with the solo oracle still holds."""
    cfg = get_smoke_config("mixtral_8x22b")
    params = tf.init_params(jax.random.key(0), cfg)
    w = cfg.sliding_window
    prompts = _prompts(cfg, (w + 8, 3), seed=2)
    eng = _mk(cfg, params, fused=True, cache_len=w)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=4))
    done = {r.rid: r.out for r in eng.run()}
    for i, p in enumerate(prompts):
        solo = _mk(cfg, params, fused=True, slots=1, cache_len=w)
        solo.submit(Request(i, p, max_new=4))
        (ref,) = solo.run()
        assert done[i] == ref.out


def test_fused_engine_counts_attention_glue_fallbacks():
    """Decode against a cache longer than the flash kernel's VMEM bound
    takes the chunked glue path; the engine counts every such traced site
    in ``stats["attn_glue_fallbacks"]`` (zero on a supported layout)."""
    base = get_smoke_config("yi_6b").replace(numerics="interp-fused")
    params = tf.init_params(jax.random.key(0), base)
    counts = {}
    for cache_len in (48, 4100):
        cfg = base.replace(name=f"yi_6b-fallback-probe-{cache_len}")
        eng = _mk(cfg, params, fused=True, slots=1, cache_len=cache_len)
        eng.submit(Request(0, _prompts(cfg, (5,))[0], max_new=3))
        eng.run()
        counts[cache_len] = eng.stats["attn_glue_fallbacks"]
    assert counts[48] == 0
    assert counts[4100] > 0


def test_fused_engine_counts_folded_attention_sites(monkeypatch):
    """Decode folds a GQA group's query heads into the rows of one tile per
    kv stripe. Every attention site of a traced tick program counts once in
    ``stats["attn_folded_sites"]``; the admission programs' 128-token
    bucket (128 x g rows, more than a tile) keeps one program per query
    head and counts none; nothing falls back to the glue path. The layer
    stack is one ``lax.scan``, traced once per program: a tick program
    holds one attention site per scanned segment."""
    from repro.numerics.ops import (ATTN_FALLBACK_KEY, ATTN_FOLD_KEY,
                                    FusedInterpNumerics,
                                    count_attention_sites)
    from repro.serve.aot import tick_chunk_sizes

    cfg = get_smoke_config("yi_6b").replace(numerics="interp-fused",
                                            name="yi_6b-fold-probe")
    assert cfg.n_heads // cfg.n_kv_heads > 1
    params = tf.init_params(jax.random.key(0), cfg)
    sites = []  # (Sq, folded, fallbacks) of each traced attention site
    real = FusedInterpNumerics.fused_attention

    def spy(self, q, *args, **kw):
        with count_attention_sites({}) as site:
            out = real(self, q, *args, **kw)
        sites.append((q.shape[1], site.get(ATTN_FOLD_KEY, 0),
                      site.get(ATTN_FALLBACK_KEY, 0)))
        return out

    monkeypatch.setattr(FusedInterpNumerics, "fused_attention", spy)
    horizon = 2
    eng = ServeEngine(cfg, params, slots=2, cache_len=128, horizon=horizon,
                      aot_buckets=(128,), max_pack=2)
    ticks = [s for s in sites if s[0] == 1]
    admits = [s for s in sites if s[0] == 128]
    assert len(ticks) + len(admits) == len(sites)
    assert len(ticks) == (len(tick_chunk_sizes(horizon))
                          * len(tf.layer_plan(cfg)))
    assert admits and all(s[1:] == (0, 0) for s in admits)
    assert all(s[1:] == (1, 0) for s in ticks)
    assert eng.stats["attn_folded_sites"] == len(ticks)
    assert eng.stats["attn_glue_fallbacks"] == 0


@pytest.mark.parametrize("numerics", ["exact", "interp-fused"])
def test_engine_counts_absorbed_mla_decode_sites(monkeypatch, numerics):
    """MLA decode attends over the latent cache in the absorbed form: every
    attention site of a traced tick program counts once in
    ``stats["attn_absorbed_sites"]`` (and, interp-fused, folds its heads
    into one tile); the admission programs attend in the expanded form and
    count none; nothing falls back to the glue path."""
    from repro.models import attention as attn
    from repro.numerics.ops import (ATTN_ABSORB_KEY, ATTN_FALLBACK_KEY,
                                    ATTN_FOLD_KEY, count_attention_sites)
    from repro.serve.aot import tick_chunk_sizes

    cfg = get_smoke_config("minicpm3_4b").replace(
        numerics=numerics, name=f"minicpm3_4b-absorb-probe-{numerics}")
    params = tf.init_params(jax.random.key(0), cfg)
    sites = []  # (Sq, absorbed, folded, fallbacks) of each traced site
    real = attn.attention_core

    def spy(q, *args, **kw):
        with count_attention_sites({}) as site:
            out = real(q, *args, **kw)
        sites.append((q.shape[1], site.get(ATTN_ABSORB_KEY, 0),
                      site.get(ATTN_FOLD_KEY, 0),
                      site.get(ATTN_FALLBACK_KEY, 0)))
        return out

    monkeypatch.setattr(attn, "attention_core", spy)
    horizon = 2
    lib = None
    if numerics != "exact":
        from repro.api import default_explorer
        lib = default_explorer().compile()
    eng = ServeEngine(cfg, params, slots=2, cache_len=128, horizon=horizon,
                      aot_buckets=(128,), max_pack=2, library=lib)
    ticks = [s for s in sites if s[0] == 1]
    admits = [s for s in sites if s[0] == 128]
    assert len(ticks) + len(admits) == len(sites)
    assert len(ticks) == (len(tick_chunk_sizes(horizon))
                          * len(tf.layer_plan(cfg)))
    assert admits and all(s[1:] == (0, 0, 0) for s in admits)
    folded = int(numerics != "exact")
    assert all(s[1:] == (1, folded, 0) for s in ticks)
    assert eng.stats["attn_absorbed_sites"] == len(ticks) > 0
    assert eng.stats["attn_glue_fallbacks"] == 0
