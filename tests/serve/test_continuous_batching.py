"""Mixed-length continuous batching: the per-slot position contract.

The engine decodes every live slot at its *own* next position. The pre-fix
engine decoded the whole pool at the single global ``max(pos)`` and then set
every slot's ``pos`` to ``pos + 1`` — a freshly admitted short-prompt request
got its KV/state rows written past its prefill position, leaving a garbage
gap and a wrong RoPE phase for the rest of its decode. The oracle is the
same engine serving one request at a time (slots=1): batching must not
change any request's greedy decode.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine

MAX_NEW = 5


def _solo_decode(cfg, params, rid, prompt, cache_len, fused=True):
    eng = ServeEngine(cfg, params, slots=1, cache_len=cache_len, fused=fused)
    eng.submit(Request(rid, prompt, max_new=MAX_NEW))
    (done,) = eng.run()
    return done.out


@pytest.mark.parametrize("arch,fused", [
    pytest.param("yi_6b", True, id="yi_6b"),
    pytest.param("minicpm3_4b", True, id="minicpm3_4b"),
    pytest.param("yi_6b", False, id="yi_6b-serial"),
    pytest.param("minicpm3_4b", False, id="minicpm3_4b-serial"),
])
def test_mixed_length_batching_matches_one_at_a_time(arch, fused):
    """Two slots, three requests of different prompt lengths: admission at
    staggered positions (the third request lands in a freed slot while the
    other slot is mid-decode at a higher position). A serial engine admits
    through the same admission program as a fused one."""
    cfg = get_smoke_config(arch)
    params = tf.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 3)]
    eng = ServeEngine(cfg, params, slots=2, cache_len=48, fused=fused)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=MAX_NEW))
    done = {r.rid: r.out for r in eng.run()}
    assert set(done) == {0, 1, 2}
    assert eng.stats["admit_dispatches"] == len(prompts)
    for i, p in enumerate(prompts):
        ref = _solo_decode(cfg, params, i, p, cache_len=48, fused=fused)
        assert done[i] == ref, f"request {i} (len {len(p)}) diverged"


def test_per_slot_positions_advance_independently():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    eng = ServeEngine(cfg, params, slots=2, cache_len=48)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       max_new=4))
    eng.submit(Request(1, rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                       max_new=4))
    eng.step()
    # after admission + one decode, each slot sits at its own position
    assert list(eng.pos) == [4 + 1, 9 + 1]


# ---------------------------------------------------------------- admission

def test_submit_rejects_prompt_longer_than_cache():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, cache_len=16)
    long_prompt = np.zeros(17, np.int32)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        eng.submit(Request(0, long_prompt, max_new=1))


def test_submit_rejects_decode_overflow():
    cfg = get_smoke_config("yi_6b")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, cache_len=16)
    with pytest.raises(ValueError, match="overflows cache_len"):
        eng.submit(Request(0, np.zeros(12, np.int32), max_new=8))
    # exactly filling the cache is fine: positions stop at cache_len - 1
    eng.submit(Request(1, np.zeros(12, np.int32), max_new=5))


def test_sliding_window_engine_accepts_long_prompts():
    """Sliding-window caches wrap; prompts beyond the window are legitimate
    (prefill stores the clipped tail position-aligned to the wrap slots).
    A cache smaller than the window is rejected at construction: every
    wrap would overwrite KV rows still inside the attention window."""
    cfg = get_smoke_config("mixtral_8x22b")
    params = tf.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, cache_len=cfg.sliding_window)
    rng = np.random.default_rng(2)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size,
                                       cfg.sliding_window + 8).astype(np.int32),
                       max_new=3))
    (done,) = eng.run()
    assert len(done.out) >= 3
    with pytest.raises(ValueError, match="retain the full attention window"):
        ServeEngine(cfg, params, slots=1, cache_len=cfg.sliding_window - 1)


def test_windowed_wrap_decode_matches_refill_oracle():
    """Regression for the wrap-slot alignment: decoding past a clipped
    windowed prefill must match re-prefilling the grown sequence (which
    masks by window with no cache wrap at all). Pre-fix, the compacted
    prefill rows were misaligned with decode's ``pos % cache`` slots, so
    the first wrapped write clobbered live in-window KV."""
    import jax.numpy as jnp

    from repro.numerics.ops import get_numerics

    # dense model + window: MoE top-k routing would amplify float noise
    # between the two computation orders into discrete expert flips
    cfg = get_smoke_config("yi_6b").replace(sliding_window=16)
    params = tf.init_params(jax.random.key(1), cfg)
    num = get_numerics("exact")
    w = cfg.sliding_window
    s = w + 5  # prompt length not a multiple of w: nonzero rotation
    rng = np.random.default_rng(4)
    seq = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
    logits, caches, _ = tf.prefill(params, jnp.asarray(seq)[None], cfg, num, w)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(3):
        logits, caches = tf.decode_step(params, tok, jnp.asarray(s + i, jnp.int32),
                                        caches, cfg, num)
        # oracle: the same sequence grown by the consumed token, re-prefilled
        seq = np.concatenate([seq, [int(tok[0, 0])]]).astype(np.int32)
        ref, _, _ = tf.prefill(params, jnp.asarray(seq)[None], cfg, num,
                               s + i + 1)
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   np.asarray(ref[:, 0], np.float32),
                                   rtol=2e-2, atol=2e-2)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


# ------------------------------------------------------------- construction

def test_library_with_exact_numerics_raises():
    """A user-passed library must not be silently discarded."""
    from repro.api import InterpLibrary
    from repro.core.table import CoeffMeta, TableDesign

    d = TableDesign(name="recip_stub", in_bits=4, out_bits=5, lookup_bits=2,
                    k=0, degree=1, sq_trunc=0, lin_trunc=0,
                    a=np.zeros(4, np.int64), b=np.zeros(4, np.int64),
                    c=np.zeros(4, np.int64),
                    a_meta=CoeffMeta(1, 0, False), b_meta=CoeffMeta(1, 0, False),
                    c_meta=CoeffMeta(1, 0, False))
    lib = InterpLibrary.from_designs([d], ["recip"])
    cfg = get_smoke_config("yi_6b")  # exact numerics
    params = tf.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="never consults"):
        ServeEngine(cfg, params, slots=1, cache_len=16, library=lib)
