"""Decode carries each scanned segment's cache stack through the layer loop.

The engine tick's layer loop holds the stacked caches as loop carry: each
layer writes its token's rows into the stack in place at its layer index
and reads its slice for attention. The oracle is the loop that served
before, kept here only: each segment scanned ``(params, caches)`` as xs
and returned the new caches as ys, concatenating the groups of a split
plan. Both run through the engine's tick program (greedy feed-back, per-
slot positions, a dead slot) and must agree bitwise in every token and
every cache leaf, for GQA, absorbed MLA, a sliding window, an SSM and a
heterogeneous plan, under ``exact`` and ``interp-fused`` numerics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import default_explorer
from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.numerics.ops import (CACHE_INPLACE_KEY, count_attention_sites,
                                get_numerics)
from repro.plan import LayerAssign, NumericsPlan, SiteAssign
from repro.serve.engine import ServeEngine, make_engine_tick

STEPS = 4


@pytest.fixture(scope="module")
def lib():
    return default_explorer().compile()


def _xs_ys_apply_segment(p_seg, seg, h, positions, cfg, numerics,
                         mode="train", caches=None, cache_len=0,
                         cross_kv=None, pos=None, layer_offset=0):
    """The decode layer loop before the stack was carried: one scan per
    group of layers with equal numerics over (params, caches) as xs, the
    layers' new caches as ys, the groups' ys concatenated."""
    assert mode == "decode"
    npat = len(seg.pattern)

    def make_body(nums):
        def body(h_in, xs):
            p_layer, cache_layer = xs
            new = {}
            for i, kind in enumerate(seg.pattern):
                h_in, new[str(i)], _ = tf.apply_block(
                    p_layer[str(i)], kind, h_in, positions, cfg, nums[i],
                    mode="decode", cache=cache_layer[str(i)],
                    cross_kv=cross_kv, pos=pos)
            return h_in, new
        return body

    def nums_at(r):
        if not hasattr(numerics, "for_layer"):
            return (numerics,) * npat
        return tuple(numerics.for_layer(layer_offset + r * npat + j)
                     for j in range(npat))

    zero = jnp.zeros((), jnp.float32)
    if seg.repeat == 1:
        h, nc = make_body(nums_at(0))(h, (p_seg, caches))
        return h, nc, zero
    groups = []  # [start, length, nums]
    for r in range(seg.repeat):
        if groups and groups[-1][2] == nums_at(r):
            groups[-1][1] += 1
        else:
            groups.append([r, 1, nums_at(r)])
    parts = []
    for start, length, nums in groups:
        def sl(x, start=start, length=length):
            return jax.lax.slice_in_dim(x, start, start + length, axis=0)
        h, nc = jax.lax.scan(make_body(nums), h,
                             (jax.tree.map(sl, p_seg),
                              jax.tree.map(sl, caches)))
        parts.append(nc)
    return h, jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *parts), zero


def _split_plan(n_layers: int, numerics: str) -> NumericsPlan:
    """Layers 0 and n-1 on the other backend: three groups, the middle one
    at an offset."""
    other = "exact" if numerics == "interp-fused" else "interp-fused"

    def la(b):
        return LayerAssign(SiteAssign(b), SiteAssign(b), SiteAssign(b))

    layers = ((la(other),) + (la(numerics),) * (n_layers - 2)
              + (la(other),))
    return NumericsPlan(layers=layers, rest=la(numerics))


# name -> (smoke preset, overrides, cache_len, prompt lengths of the live
# slots); one more slot stays dead (never admitted, frozen at position 0)
CASES = {
    "gqa": ("yi_6b", {}, 32, (5, 11)),
    "mla": ("minicpm3_4b", {}, 32, (7, 3)),
    "window": ("mixtral_8x22b", {}, 64, (30, 37)),
    "ssm": ("mamba2_130m", {}, 32, (6, 9)),
    "plan": ("yi_6b", {"n_layers": 5}, 32, (4, 12)),
}


def _setup(case: str, numerics: str, lib):
    arch, over, cache_len, lens = CASES[case]
    cfg = get_smoke_config(arch).replace(numerics=numerics, **over)
    if case == "plan":
        cfg = cfg.replace(plan=_split_plan(cfg.n_layers, numerics))
    library = None if numerics == "exact" and case != "plan" else lib
    params = tf.init_params(jax.random.key(1), cfg)
    nums = get_numerics(cfg, library, fused=True)
    slots = len(lens) + 1
    pool = tf.init_cache(cfg, slots, cache_len)
    rng = np.random.default_rng(3)
    tok = np.zeros((slots, 1), np.int32)
    pos = np.zeros((slots,), np.int32)
    for s, n in enumerate(lens):
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)),
                             jnp.int32)
        logits, one, _ = tf.prefill(params, prompt, cfg, nums, cache_len)
        pool = tf.splice_cache(cfg, pool, one, s)
        tok[s, 0], pos[s] = int(jnp.argmax(logits[0, -1])), n
    live = np.arange(slots) < len(lens)
    return cfg, library, params, pool, (jnp.asarray(tok), jnp.asarray(pos),
                                        jnp.asarray(live))


@pytest.mark.parametrize("numerics", ["exact", "interp-fused"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_carried_stack_decode_matches_xs_ys_oracle(lib, monkeypatch, case,
                                                   numerics):
    cfg, library, params, pool, (tok, pos, live) = _setup(case, numerics,
                                                          lib)
    if case == "window":
        assert int(pos.max()) + STEPS > cfg.sliding_window  # rows wrap
    scanned = sum(seg.repeat > 1 for seg in tf.layer_plan(cfg))
    assert scanned >= 1

    def run():
        tick = jax.jit(make_engine_tick(cfg, STEPS))
        with count_attention_sites({}) as sink:
            toks, _, pos_out, ok, caches = tick(params, tok, pos, live, pool,
                                                library=library)
        return sink.get(CACHE_INPLACE_KEY, 0), toks, pos_out, ok, caches

    sites, *got = run()
    with monkeypatch.context() as m:
        m.setattr(tf, "apply_segment", _xs_ys_apply_segment)
        oracle_sites, *want = run()
    assert (sites, oracle_sites) == (scanned, 0)
    assert bool(jnp.all(got[2]))  # every slot's logits finite
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    flat_got, tree_got = jax.tree.flatten(got[3])
    flat_want, tree_want = jax.tree.flatten(want[3])
    assert tree_got == tree_want
    for a, b in zip(flat_got, flat_want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["yi_6b", "minicpm3_4b"])
def test_engine_counts_cache_inplace_sites(lib, arch):
    """Every traced tick program counts each scanned decode segment once
    in ``stats["cache_inplace_sites"]``; the admission programs (prefill)
    count none."""
    from repro.serve.aot import tick_chunk_sizes

    cfg = get_smoke_config(arch).replace(numerics="interp-fused",
                                         name=f"{arch}-inplace-probe")
    params = tf.init_params(jax.random.key(0), cfg)
    horizon = 2
    eng = ServeEngine(cfg, params, slots=2, cache_len=128, horizon=horizon,
                      aot_buckets=(128,), max_pack=2, library=lib)
    assert eng.stats["aot_compiles"] > len(tick_chunk_sizes(horizon))
    scanned = sum(seg.repeat > 1 for seg in tf.layer_plan(cfg))
    assert eng.stats[CACHE_INPLACE_KEY] == (
        len(tick_chunk_sizes(horizon)) * scanned) > 0
