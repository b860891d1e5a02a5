"""AOT bucket-table edge cases (DESIGN.md §17, satellite of the sharded
serving tier): exact-boundary prompts, prompts past the largest bucket
(exact-length fallback, counted), and mixed-bucket admission ordering vs
the one-request-at-a-time oracle."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.serve.aot import BucketTable, pack_sizes, tick_chunk_sizes
from repro.serve.engine import Request, ServeEngine

MAX_NEW = 5
CACHE = 48


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    return cfg, params


def _serve(cfg, params, prompts, **kw):
    eng = ServeEngine(cfg, params, cache_len=CACHE, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=MAX_NEW))
    return {r.rid: r.out for r in eng.run()}, eng


# -- BucketTable semantics -------------------------------------------------

def test_bucket_table_validation():
    with pytest.raises(ValueError):
        BucketTable(())
    with pytest.raises(ValueError):
        BucketTable((16, 8))  # not ascending
    with pytest.raises(ValueError):
        BucketTable((8, 8))  # duplicate
    with pytest.raises(ValueError):
        BucketTable((0, 8))  # non-positive


def test_bucket_for_boundary_and_overflow():
    bt = BucketTable((8, 16, 32))
    assert bt.bucket_for(1) == 8
    assert bt.bucket_for(8) == 8  # exact boundary stays in its bucket
    assert bt.bucket_for(9) == 16
    assert bt.bucket_for(32) == 32
    assert bt.bucket_for(33) is None  # past the largest: fallback


def test_for_cache_clips_and_degenerates():
    assert BucketTable.for_cache(20, (8, 16, 32)).buckets == (8, 16)
    # nothing fits -> one full-cache bucket, never an empty table
    assert BucketTable.for_cache(4, (8, 16)).buckets == (4,)


def test_pack_and_chunk_sizes():
    assert pack_sizes(4, 8) == (1, 2, 4)
    assert pack_sizes(8, 3) == (1, 2)  # capped by the slot pool
    assert tick_chunk_sizes(8) == (1, 2, 4, 8)
    assert tick_chunk_sizes(6) == (1, 2, 4)


# -- engine behavior on the edges ------------------------------------------

def test_prompt_exactly_at_bucket_boundary(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (8, 16)]  # both exactly on a bucket edge
    got, eng = _serve(cfg, params, prompts, slots=2, aot_buckets=(8, 16))
    ref, _ = _serve(cfg, params, prompts, slots=2)
    assert got == ref
    assert eng.stats["aot_fallbacks"] == 0
    assert eng.stats["aot_misses"] == 0


def test_prompt_longer_than_largest_bucket_falls_back(setup):
    cfg, params = setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (20, 5)]  # 20 > largest bucket 16
    got, eng = _serve(cfg, params, prompts, slots=2, aot_buckets=(8, 16))
    ref, _ = _serve(cfg, params, prompts, slots=2)
    assert got == ref
    assert eng.stats["aot_fallbacks"] == 1  # the oversized prompt, counted
    assert len(got[0]) == MAX_NEW  # and still fully served


def test_mixed_bucket_admission_matches_solo_oracle(setup):
    """Requests landing in different buckets, more requests than slots:
    admission order (queue order -> ascending free slots) must reproduce
    the one-request-at-a-time oracle exactly, packed dispatch or not."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    lens = (5, 16, 3, 9, 30, 8, 2, 11)  # mixes buckets + one fallback
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    got, eng = _serve(cfg, params, prompts, slots=3,
                      aot_buckets=(8, 16), max_pack=4)
    assert eng.stats["packed_requests"] > 0
    for i, p in enumerate(prompts):
        solo, _ = _serve(cfg, params, [p], slots=1)
        assert got[i] == solo[0], f"request {i} (len {len(p)}) diverged"


@pytest.mark.parametrize("arch", ["mamba2_130m", "deepseek_moe_16b"])
def test_unpackable_config_admits_each_request_at_its_own_length(arch):
    """A config that cannot be padded (cumulative SSM state; MoE capacity
    that grows with the padded length) ignores the bucket table at
    admission: one own-length dispatch per request, nothing packed or
    counted as a fallback, and the streams of one-request-at-a-time
    engines."""
    cfg = get_smoke_config(arch)
    assert not tf.packable(cfg)
    params = tf.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 3, 7)]
    got, eng = _serve(cfg, params, prompts, slots=2, aot_buckets=(8, 16))
    assert eng.stats["packed_admits"] == 0
    assert eng.stats["packed_requests"] == 0
    assert eng.stats["aot_fallbacks"] == 0
    assert eng.stats["admit_dispatches"] == len(prompts)
    for i, p in enumerate(prompts):
        solo, _ = _serve(cfg, params, [p], slots=1)
        assert got[i] == solo[0], f"request {i} (len {len(p)}) diverged"


def test_warm_engine_steady_state_has_zero_misses(setup):
    cfg, params = setup
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 7, 12, 15)]
    got, eng = _serve(cfg, params, prompts, slots=4, aot_buckets=(8, 16))
    assert eng.stats["aot_misses"] == 0
    assert eng.stats["aot_hits"] > 0
    assert sum(len(v) for v in got.values()) == MAX_NEW * len(prompts)
