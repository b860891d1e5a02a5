"""Padded (bucketed) prefill and the one rule that decides where it applies.

``tf.packable`` owns the decision; ``tf.prefill_padded`` refuses every
config it rejects. Where it accepts, each right-padded row must give the
logits and the live cache positions of an exact-length prefill of that
prompt alone — the property packed admission relies on."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.models import transformer as tf
from repro.numerics.ops import get_numerics

LENS, BUCKET, CACHE = (5, 11), 16, 32


def _pos_leaves(caches):
    flat, _ = jax.tree_util.tree_flatten_with_path(caches)
    return [np.asarray(leaf) for path, leaf in flat
            if str(getattr(path[-1], "key", path[-1])).lstrip(".") == "pos"]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_packable_decides_prefill_padded(arch):
    cfg = get_smoke_config(arch)
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in LENS]
    toks = np.zeros((len(LENS), BUCKET), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    if not tf.packable(cfg):
        with pytest.raises(ValueError, match="^prefill_padded: "):
            tf.prefill_padded(None, jnp.asarray(toks), jnp.asarray(LENS),
                              cfg, get_numerics("exact"), CACHE)
        return
    params = tf.init_params(jax.random.key(0), cfg)
    num = get_numerics("exact")
    logits, caches, _ = tf.prefill_padded(params, jnp.asarray(toks),
                                          jnp.asarray(LENS), cfg, num, CACHE)
    for i, r in enumerate(rows):
        ref, ref_caches, _ = tf.prefill(params, jnp.asarray(r)[None], cfg,
                                        num, CACHE)
        np.testing.assert_allclose(np.asarray(logits[i], np.float32),
                                   np.asarray(ref[0], np.float32),
                                   rtol=1e-5, atol=1e-5)
        row = tf.extract_cache_row(cfg, caches, i)
        got_pos, ref_pos = _pos_leaves(row), _pos_leaves(ref_caches)
        assert got_pos and len(got_pos) == len(ref_pos)
        for g, e in zip(got_pos, ref_pos):
            np.testing.assert_array_equal(g, e)
