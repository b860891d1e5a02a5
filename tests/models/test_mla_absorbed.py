"""Absorbed-latent MLA decode on the small MiniCPM3 preset, seeded random
weights, on the CPU.

Decode attends over the latent cache directly: ``wkv_b``'s key half takes
the query into the latent space, its value half brings the latent output
back up, and the cache is read as it lies. Oracles: (1) the plain float32
reference's full forward (``bench/reference/minicpm3.py``) after prefill
and cached decode, under ``exact`` and ``interp-fused``; (2) the expanded
decode that served MLA before — the whole latent cache brought up to
per-head K and V every step — kept here as a test oracle only, on the same
cache with per-slot positions.
"""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import default_explorer
from repro.configs.base import get_smoke_config
from repro.models import attention as attn
from repro.models import transformer as tf
from repro.numerics.ops import get_numerics

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import weights  # noqa: E402
from bench.reference import minicpm3 as ref  # noqa: E402

# Tolerances, relative to the largest reference logit (or output). Exact
# numerics in float32 differ from the reference only by the order of
# float32 sums (measured 4e-7): 1e-5 fails any bfloat16 rounding on the
# path (2^-8 relative). Interp-fused numerics carry the tables' error
# (exp, recip, rsqrt, SiLU) through two layers: measured 2.8e-3 over the
# decoded rows, 1.8e-3 at the prefilled row, which has no decode on it.
TOL = {"exact": 1e-5, "interp-fused": 4e-3}


@pytest.fixture(scope="module")
def lib():
    return default_explorer().compile()


def _cfg(numerics: str):
    return get_smoke_config("minicpm3_4b").replace(numerics=numerics)


def _hf(cfg) -> dict:
    """The preset as published keys; the program's RMSNorm eps, so that
    only the datapath differs from the reference."""
    m = cfg.mla
    return dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads, q_lora_rank=m.q_lora_rank,
                kv_lora_rank=m.kv_lora_rank,
                qk_nope_head_dim=m.qk_nope_head_dim,
                qk_rope_head_dim=m.qk_rope_head_dim,
                v_head_dim=m.v_head_dim, intermediate_size=cfg.d_ff,
                vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
                rms_norm_eps=1e-6, torch_dtype=cfg.param_dtype)


def _numerics(name: str, lib):
    return get_numerics(name, library=None if name == "exact" else lib)


@pytest.mark.parametrize("numerics", ["exact", "interp-fused"])
def test_prefill_then_absorbed_decode_matches_reference(lib, numerics):
    cfg = _cfg(numerics)
    hf = _hf(cfg)
    params = weights.make(ref.param_shapes(hf), 5)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    more = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    nums = _numerics(numerics, lib)
    with jax.default_matmul_precision("highest"):
        logits, cache, _ = tf.prefill(params, jnp.asarray(prompt)[None], cfg,
                                      nums, 64)
        got = [np.asarray(logits[0, -1])]
        for i, t in enumerate(more[:-1]):
            lg, cache = tf.decode_step(params, jnp.asarray([[t]]),
                                       jnp.int32(len(prompt) + i), cache,
                                       cfg, nums)
            got.append(np.asarray(lg[0, -1]))
    seq = np.concatenate([prompt, more[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    want = np.asarray(ref.logits(params, hf, seq, rows))
    err = np.abs(np.stack(got) - want).max()
    assert err <= TOL[numerics] * np.abs(want).max(), (err,
                                                        np.abs(want).max())


def _expanded_decode(p, x, pos, cache, cfg, numerics):
    """The expanded MLA decode: the cache written as ``mla_decode`` writes
    it, then every cached latent brought up to per-head K (no-position
    part and shared rotary key) and V, and attended per head."""
    b = x.shape[0]
    pos, positions = attn._decode_positions(pos, b)
    q = attn._mla_q(p, x, positions, cfg, numerics)
    ckv, kr = attn._mla_kv_latent(p, x, positions, cfg, numerics)
    upd = jax.vmap(lambda buf, new, s:
                   jax.lax.dynamic_update_slice(buf, new, (s, 0)))
    ck, krb = upd(cache.k, ckv, pos), upd(cache.v, kr, pos)
    pc = jax.vmap(lambda buf, new, s: jax.lax.dynamic_update_slice(
        buf, new, (s,)))(cache.pos, positions, pos)
    k, v = attn._mla_expand(p, ck, krb, cfg)
    o = attn.attention_core(q, k, v, positions, pc, numerics, causal=True,
                            kv_chunk=min(4096, k.shape[1]))
    return o.reshape(b, 1, -1) @ p["wo"], attn.KVCache(ck, krb, pc)


# Same cache, same weights, float32: the two forms differ only in the
# order of float32 sums (scores q.(W_k c) against (q W_k).c), measured
# 1.3e-7 (exact) and 1.7e-7 (interp-fused) of the largest output.
# Interp-fused reads the exp table at scores so moved, where one code may
# step (its lsb, ~1e-5 of an output); a bfloat16 absorbed query or latent
# output (2^-9) misses either limit by more than an order of magnitude.
TOL_EXPANDED = {"exact": 1e-5, "interp-fused": 1e-4}


@pytest.mark.parametrize("numerics", ["exact", "interp-fused"])
def test_absorbed_decode_matches_expanded_decode(lib, numerics):
    """Two slots at their own positions (7 and 4 filled), a third whose
    cache is dead but for the token it writes; one decode step of layer 0."""
    cfg = _cfg(numerics)
    nums = _numerics(numerics, lib)
    params = weights.make(ref.param_shapes(_hf(cfg)), 3)
    p = jax.tree.map(lambda a: a[0],
                     params["segments"]["seg0"]["0"])["mixer"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (3, 7, cfg.d_model)).astype(np.float32))
    positions = jnp.broadcast_to(jnp.arange(7, dtype=jnp.int32), (3, 7))
    with jax.default_matmul_precision("highest"):
        _, cache = attn.mla_prefill(p, x, positions, cfg, nums, 32)
        cache = cache._replace(pos=cache.pos.at[1, 4:].set(-1)
                               .at[2].set(-1))
        pos = jnp.asarray([7, 4, 0], jnp.int32)
        xd = jnp.asarray(rng.normal(0, 1, (3, 1, cfg.d_model))
                         .astype(np.float32))
        y, c1 = attn.mla_decode(p, xd, pos, cache, cfg, nums)
        y0, c0 = _expanded_decode(p, xd, pos, cache, cfg, nums)
    for a, b in zip(c1, c0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    err = np.abs(np.asarray(y) - np.asarray(y0)).max()
    assert err <= TOL_EXPANDED[numerics] * np.abs(np.asarray(y0)).max(), err
