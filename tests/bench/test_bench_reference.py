"""The plain references against the served program with ``exact`` numerics
at smoke size: prefill of a prompt, then cached decode of further tokens,
compared logit by logit with the reference's full forward pass."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from repro.models import transformer as tf
from repro.numerics.ops import get_numerics

# float32 on both sides; the reference uses the published RMSNorm eps
# (1e-5) where the program uses 1e-6, which moves logits by ~1e-5
TOL = 2e-4


@pytest.mark.parametrize("workload", ["yi_smoke.exact.tiny",
                                      "minicpm_smoke.interp.tiny"])
def test_reference_matches_prefill_then_cached_decode(smoke_root, workload):
    cell = harness.load_cell(workload, smoke_root)
    cfg = harness.model_config(cell).replace(numerics="exact")
    ref = harness.module("reference", cell.arch, cell.bench_dir)
    params = harness.make_weights(cell, 5)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    more = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    nums = get_numerics("exact")
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
    want = np.asarray(ref.logits(weights.make(ref.param_shapes(cell.hf), 5),
                                 cell.hf, seq, rows))
    got = np.stack(got)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= TOL * scale, (
        np.abs(got - want).max(), scale)
