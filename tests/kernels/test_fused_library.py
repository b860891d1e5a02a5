"""Fused library-bound datapath: golden bit-exactness of the in-kernel ROM
reads against the per-table ``table_eval_int`` oracle (every library kind),
bitwise equivalence of the library softmax/rmsnorm variants with the
per-table kernels, and the position-masked flash variant vs its oracle.

Bit-identity contract (ISSUE 5): the *integer* datapath of every fused
variant — ROM row select, coefficient gather, truncations, Horner, final
shift — is bit-identical to ``table_eval_int``; the composed float kernels
share one glue implementation with their per-table twins, so those pairs
are bitwise equal end to end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DEFAULT_LIBRARY_KINDS, default_explorer
from repro.kernels.flashattn.ops import attention_fused, attention_fused_library
from repro.kernels.interp.kernel import rom_eval_2d
from repro.kernels.rmsnorm.ops import approx_rmsnorm_fused, approx_rmsnorm_library
from repro.kernels.softmax.ops import (approx_softmax_fused,
                                       approx_softmax_library, lib_meta)
from repro.numerics.ops import get_numerics, table_eval_int


@pytest.fixture(scope="module")
def lib():
    return default_explorer().compile()


# ---------------------------------------------------------------------------
# per-kind golden: the fused consumers' in-kernel ROM datapath (_lut_rom)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", DEFAULT_LIBRARY_KINDS)
def test_rom_lut_golden_vs_table_eval_int(lib, kind):
    """Exhaustive per-kind sweep of `_lut_rom` — the exact datapath the
    fused softmax/rmsnorm/flashattn kernels evaluate in-registers — against
    the per-table oracle."""
    m = lib_meta(lib, kind)
    codes = np.arange(1 << m["in_bits"], dtype=np.int32)
    pad = (-codes.size) % (8 * 128)
    tiled = jnp.asarray(np.pad(codes, (0, pad)).reshape(-1, 128))
    out = rom_eval_2d(tiled, lib.coeffs.reshape(-1, 3), fid=m["fid"],
                      r_max=lib.coeffs.shape[1], **m["eval"], interpret=True)
    got = np.asarray(out).reshape(-1)[: codes.size]
    ref = np.asarray(table_eval_int(jnp.asarray(codes),
                                    default_explorer().get_table(kind)))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# fused softmax / rmsnorm: library variant == per-table variant, bit for bit
# ---------------------------------------------------------------------------

def test_library_softmax_bitwise_equals_per_table(lib):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (16, 128)).astype(np.float32))
    per_table = np.asarray(approx_softmax_fused(x, use_kernel=True,
                                                interpret=True))
    lib_kernel = np.asarray(approx_softmax_library(x, lib, use_kernel=True,
                                                   interpret=True))
    lib_ref = np.asarray(approx_softmax_library(x, lib, use_kernel=False))
    np.testing.assert_array_equal(lib_kernel, per_table)
    np.testing.assert_array_equal(lib_kernel, lib_ref)


def test_library_rmsnorm_bitwise_equals_per_table(lib):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 2, (16, 128)).astype(np.float32))
    gamma = jnp.asarray(rng.normal(1, 0.1, 128).astype(np.float32))
    per_table = np.asarray(approx_rmsnorm_fused(x, gamma, use_kernel=True,
                                                interpret=True))
    lib_kernel = np.asarray(approx_rmsnorm_library(x, gamma, lib,
                                                   use_kernel=True,
                                                   interpret=True))
    lib_ref = np.asarray(approx_rmsnorm_library(x, gamma, lib,
                                                use_kernel=False))
    np.testing.assert_array_equal(lib_kernel, per_table)
    np.testing.assert_array_equal(lib_kernel, lib_ref)


def test_library_softmax_unaligned_shapes(lib):
    """Off the 128-lane grid the wrapper runs the jnp ROM oracle — any
    trailing dim, any leading shape."""
    rng = np.random.default_rng(2)
    for shape in [(5,), (3, 33), (2, 4, 17)]:
        x = jnp.asarray(rng.normal(0, 3, shape).astype(np.float32))
        out = np.asarray(approx_softmax_library(x, lib))
        assert out.shape == shape
        np.testing.assert_allclose(out.sum(-1), 1.0, atol=5e-3)


# ---------------------------------------------------------------------------
# flash attention: library variant vs per-table kernel and vs the oracle
# ---------------------------------------------------------------------------

def test_library_flash_bitwise_equals_per_table_kernel(lib):
    """On the training layout (arange positions) the library kernel runs the
    same chunk math as the per-table kernel over the same ROM rows — bitwise
    equal."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 256, 2, 128))
                           .astype(np.float32)) for _ in range(3))
    for causal in (True, False):
        a = np.asarray(attention_fused(q, k, v, causal=causal,
                                       use_kernel=True, interpret=True))
        b = np.asarray(attention_fused_library(q, k, v, lib, causal=causal,
                                               use_kernel=True,
                                               interpret=True))
        np.testing.assert_array_equal(a, b)


def test_library_flash_grouped_kv_matches_expanded(lib):
    """GQA: unexpanded (kvh < h) K/V through the kernel's index-mapped kv
    stripes == caller-expanded heads, bitwise (same programs per row).
    Sq * g = 256 rows exceed one tile, so the group is not folded."""
    rng = np.random.default_rng(9)
    b, s, h, kvh, d = 2, 128, 4, 2, 64
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, s, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, s, kvh, d)).astype(np.float32))
    kx = jnp.repeat(k, h // kvh, axis=2)
    vx = jnp.repeat(v, h // kvh, axis=2)
    for use_kernel in (True, False):
        grouped = np.asarray(attention_fused_library(
            q, k, v, lib, causal=True, use_kernel=use_kernel, interpret=True))
        expanded = np.asarray(attention_fused_library(
            q, kx, vx, lib, causal=True, use_kernel=use_kernel,
            interpret=True))
        np.testing.assert_array_equal(grouped, expanded)


# (batch, q len, heads, kv heads, filled kv slots per row (0: dead slot),
# window); Sk 256 with d 128, two kv chunks per stripe
FOLD = {
    "decode-g8-dead-slot": (3, 1, 8, 1, (0, 100, 256), None),
    "decode-g6-pad-rows": (2, 1, 12, 2, (37, 200), None),
    "decode-g8-window": (2, 1, 8, 1, (180, 250), 64),
    "chunk4-g8-causal": (2, 4, 8, 1, (60, 200), None),
}


@pytest.mark.parametrize("case", sorted(FOLD))
def test_library_flash_folded_group_bitwise_equals_unfolded(lib, case):
    """Decode-sized calls fold a GQA group's query heads into the rows of
    one tile per kv stripe; the same K/V with the heads expanded (group 1)
    keep one program per query head. The two give the same output, bit
    for bit: each row keeps its own positions, masks and online softmax,
    and a folded tile holds the same query positions as an unfolded one,
    so every chunk's liveness is the same too."""
    from repro.numerics.ops import ATTN_FOLD_KEY, count_attention_sites

    b, sq, h, kvh, fills, window = FOLD[case]
    d, sk = 128, 256
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(0, 1, (b, sq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, sk, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, sk, kvh, d)).astype(np.float32))
    kv_pos = np.full((b, sk), -1, np.int32)
    q_pos = np.zeros((b, sq), np.int32)
    for i, n in enumerate(fills):
        kv_pos[i, :n] = np.arange(n)
        q_pos[i] = np.arange(max(n - sq, 0), max(n - sq, 0) + sq)
    kw = dict(causal=True, window=window, q_pos=jnp.asarray(q_pos),
              kv_pos=jnp.asarray(kv_pos), interpret=True)
    g = h // kvh
    sink: dict = {}
    with count_attention_sites(sink):
        folded = np.asarray(attention_fused_library(
            q, k, v, lib, use_kernel=True, **kw))
    assert sink[ATTN_FOLD_KEY] == 1
    with count_attention_sites(sink):
        unfolded = np.asarray(attention_fused_library(
            q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), lib,
            use_kernel=True, **kw))
    assert sink[ATTN_FOLD_KEY] == 1
    np.testing.assert_array_equal(folded, unfolded)
    live = np.asarray(fills) > 0  # a dead slot's row is never read
    ref = np.asarray(attention_fused_library(q, k, v, lib, use_kernel=False,
                                             **kw))
    np.testing.assert_allclose(folded[live], ref[live], rtol=5e-2,
                               atol=5e-3)


def test_library_flash_decode_masking_matches_ref_and_glue(lib):
    """Decode shape: Sq=1 against a partially-filled cache with per-row
    positions and dead slots. Kernel vs unchunked lib oracle vs the chunked
    attention_core glue path (table error budget only)."""
    from repro.models.attention import attention_core

    rng = np.random.default_rng(4)
    b, h, d, sk = 2, 2, 64, 48
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.normal(0, 1, (b, sk, h, d)).astype(np.float32))
    vc = jnp.asarray(rng.normal(0, 1, (b, sk, h, d)).astype(np.float32))
    kv_pos = np.full((b, sk), -1, np.int32)
    kv_pos[0, :10] = np.arange(10)
    kv_pos[1, :20] = np.arange(20)
    q_pos = np.array([[9], [19]], np.int32)
    kw = dict(causal=True, q_pos=jnp.asarray(q_pos), kv_pos=jnp.asarray(kv_pos))
    kern = np.asarray(attention_fused_library(q, kc, vc, lib,
                                              use_kernel=True,
                                              interpret=True, **kw))
    ref = np.asarray(attention_fused_library(q, kc, vc, lib,
                                             use_kernel=False, **kw))
    np.testing.assert_allclose(kern, ref, rtol=5e-2, atol=5e-3)
    glue = np.asarray(attention_core(q, kc, vc, jnp.asarray(q_pos),
                                     jnp.asarray(kv_pos),
                                     get_numerics("interp"), causal=True))
    np.testing.assert_allclose(kern, glue, rtol=5e-2, atol=5e-3)


def test_library_flash_sliding_window(lib):
    """The window mask drops exactly the out-of-window positions (vs the
    oracle with the same mask semantics as models.attention._mask)."""
    rng = np.random.default_rng(5)
    b, s, h, d, w = 1, 64, 1, 64, 16
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
               for _ in range(3))
    kern = np.asarray(attention_fused_library(q, k, v, lib, causal=True,
                                              window=w, use_kernel=True,
                                              interpret=True))
    ref = np.asarray(attention_fused_library(q, k, v, lib, causal=True,
                                             window=w, use_kernel=False))
    np.testing.assert_allclose(kern, ref, rtol=5e-2, atol=5e-3)
    # windowed result must differ from unwindowed (the mask is live)
    full = np.asarray(attention_fused_library(q, k, v, lib, causal=True,
                                              use_kernel=False))
    assert np.abs(ref - full).max() > 1e-3


# ---------------------------------------------------------------------------
# fused numerics backend: routing + model-stack integration
# ---------------------------------------------------------------------------

def test_fused_numerics_requires_library():
    with pytest.raises(ValueError, match="needs a compiled InterpLibrary"):
        get_numerics("interp", None, fused=True)
    with pytest.raises(ValueError, match="needs a compiled InterpLibrary"):
        get_numerics("interp-fused")


def test_fused_numerics_softmax_matches_library_kernel(lib):
    num = get_numerics("interp", lib, fused=True)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(0, 3, (8, 128)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(num.softmax(x)),
        np.asarray(approx_softmax_library(x, lib)))
    # non-last-axis softmax falls back to the glue path (still normalized)
    y = np.asarray(num.softmax(x, axis=0))
    np.testing.assert_allclose(y.sum(0), 1.0, atol=5e-3)
    gamma = jnp.ones(128, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(num.rmsnorm(x, gamma)),
        np.asarray(approx_rmsnorm_library(x, gamma, lib)))


def test_fused_numerics_close_to_glue_numerics(lib):
    """Same certified tables, different code derivation for the reciprocal
    (bit-twiddle vs frexp): composite outputs agree within a table ulp."""
    fused = get_numerics("interp", lib, fused=True)
    glue = get_numerics("interp", lib)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 3, (8, 128)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(fused.softmax(x)),
                               np.asarray(glue.softmax(x)), atol=2e-3)
    gamma = jnp.ones(128, jnp.float32)
    np.testing.assert_allclose(np.asarray(fused.rmsnorm(x, gamma)),
                               np.asarray(glue.rmsnorm(x, gamma)),
                               rtol=3e-3, atol=3e-3)


def test_fused_attention_refusal_is_counted(lib):
    """A layout the fused kernel refuses returns None (the chunked glue
    path takes over) and is counted in the active trace-time sink — the
    engine's ``stats["attn_glue_fallbacks"]`` — never silently."""
    from repro.numerics.ops import (ATTN_FALLBACK_KEY,
                                    count_attention_sites)

    num = get_numerics("interp", lib, fused=True)
    q = jnp.zeros((1, 4, 3, 16), jnp.float32)  # 3 heads over 2 kv heads
    k = v = jnp.zeros((1, 4, 2, 16), jnp.float32)
    pos = jnp.zeros((1, 4), jnp.int32)
    sink: dict = {}
    with count_attention_sites(sink):
        out = num.fused_attention(q, k, v, pos, pos, causal=True,
                                  window=None, scale=None)
    assert out is None and sink[ATTN_FALLBACK_KEY] == 1
    # outside any sink nothing is recorded, and a supported layout is not
    # a refusal
    assert num.fused_attention(q, k, v, pos, pos, causal=True, window=None,
                               scale=None) is None
    with count_attention_sites(sink):
        out = num.fused_attention(q[:, :, :2], k, v, pos, pos, causal=True,
                                  window=None, scale=None)
    assert out is not None and sink[ATTN_FALLBACK_KEY] == 1


# (batch, query heads, filled kv slots per row (0: dead slot)); Sk 256,
# latent 256 and rotary key 32 wide (MiniCPM3's), two kv chunks a stripe
ABSORBED = {
    "h12-pad-rows-dead-slot": (3, 12, (0, 100, 256)),
    "h40-per-slot-positions": (2, 40, (37, 200)),
}


@pytest.mark.parametrize("case", sorted(ABSORBED))
def test_library_flash_absorbed_latent_matches_oracle(lib, case):
    """Absorbed MLA decode: every query head of a slot is a row of one
    program over the slot's latent stripe, which is the keys' first part
    and the values; the shared rotary stripe is the keys' second part;
    the scale is 1/sqrt(96), the published head width, not the operands'
    Dc + Dr. The kernel in interpret mode against its unchunked jnp
    oracle, and against float32 softmax attention over the concatenated
    keys."""
    from repro.numerics.ops import (ATTN_FOLD_KEY, count_attention_sites,
                                    softmax_ulp_bound)

    b, h, fills = ABSORBED[case]
    dc, dr, sk, scale = 256, 32, 256, 96 ** -0.5
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, dc)).astype(np.float32))
    qr = jnp.asarray(rng.normal(0, 1, (b, 1, h, dr)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 1, (b, sk, 1, dc)).astype(np.float32))
    kr = jnp.asarray(rng.normal(0, 1, (b, sk, 1, dr)).astype(np.float32))
    kv_pos = np.full((b, sk), -1, np.int32)
    q_pos = np.zeros((b, 1), np.int32)
    for i, n in enumerate(fills):
        kv_pos[i, :n] = np.arange(n)
        q_pos[i] = max(n - 1, 0)
    kw = dict(causal=True, q_pos=jnp.asarray(q_pos),
              kv_pos=jnp.asarray(kv_pos), q_rope=qr, k_rope=kr, scale=scale,
              interpret=True)
    sink: dict = {}
    with count_attention_sites(sink):
        got = np.asarray(attention_fused_library(q, c, c, lib,
                                                 use_kernel=True, **kw))
    assert sink[ATTN_FOLD_KEY] == 1 and got.shape == (b, 1, h, dc)
    oracle = np.asarray(attention_fused_library(q, c, c, lib,
                                                use_kernel=False, **kw))
    live = np.asarray(fills) > 0  # a dead slot's rows are never read
    vmax = np.abs(np.asarray(c)).max()
    # same table reads; the kernel renormalizes chunk by chunk against a
    # running max, so exp codes can differ by their lsb (measured 2.6e-5 of
    # max |v|); values rounded to bfloat16 (2^-9) would fail
    np.testing.assert_allclose(got[live], oracle[live], rtol=0,
                               atol=1e-4 * vmax)
    s = (np.einsum("bqhd,bkd->bhqk", np.asarray(q), np.asarray(c)[:, :, 0])
         + np.einsum("bqhd,bkd->bhqk", np.asarray(qr),
                     np.asarray(kr)[:, :, 0])) * scale
    s = np.where((kv_pos >= 0)[:, None, None, :], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkd->bqhd", p, np.asarray(c)[:, :, 0])
    # the tables' certified softmax error bounds each weight's relative
    # error, so an output is off by at most that share of max |v| (measured
    # 0.07 of the bound); a scale of 1/sqrt(Dc + Dr) misses it by far
    bound = softmax_ulp_bound(lib.meta("exp2neg"), lib.meta("recip"))
    err = np.abs(got[live] - want[live]).max()
    assert err <= bound * vmax, (err, bound)
