"""Pallas TPU kernel: fused flash attention with table-backed exp/recip.

The structural answer to the §Perf Cell-B memory term: the score block,
mask, exponential, running renormalization and PV product live entirely in
VMEM — HBM sees only Q/K/V reads and one output write per tile. Both
transcendentals come from the paper's certified tables (the same `_lut`
SMEM ROM-select datapath as kernels/softmax), so the fused kernel *is* the
generated hardware of Fig. 1 dropped into the attention hot loop.

Tiling: grid (N heads-batch, Sq/BLOCK_Q); per step the q tile (BLOCK_Q, D)
and the full K/V stripe (Sk, D) for that head are VMEM-resident (bf16
Sk=4k, D=128 -> 2 MB; longer Sk moves kv onto the grid axis — documented
bound). The kv loop runs in BLOCK_K chunks with `pl.when`-guarded compute:
causally-dead chunks are skipped (perf iteration B1 inside the kernel).

The absorbed-latent variant (``mla_flash_lib``, MLA decode) runs the same
recurrence with keys in two parts — the latent stripe and the shared
rotary stripe — and the latent stripe as its values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.interp.kernel import (_lut, _lut_rom, flat_rom, pow2,
                                         rom_spec)

BLOCK_Q = 128
BLOCK_K = 128
LOG2E = 1.4426950408889634
NEG = -1e30
M_FLOOR = -1e20


def _table_exp_neg(t, lut, meta):
    """2^(-t) for t >= 0 via the exp2neg table (exact power-of-2 scaling).
    ``lut``: int32 codes -> integer table output (per-table or library-ROM
    closure — one copy of the glue for both kernel variants)."""
    t = jnp.minimum(t, 126.0)
    n = jnp.floor(t)
    frac = t - n
    eb = meta["in_bits"]
    codes = jnp.clip(jnp.round(frac * (1 << eb)).astype(jnp.int32),
                     0, (1 << eb) - 1)
    tab = lut(codes).astype(jnp.float32)
    return tab * (2.0 ** -meta["out_bits"]) * pow2(-n)


def _table_recip(s, lut, meta):
    """1/s for s > 0 via IEEE-754 mantissa split + reciprocal table."""
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    expo = jnp.bitwise_and(jax.lax.shift_right_logical(bits, 23), 255) - 127
    mant = jnp.bitwise_and(bits, (1 << 23) - 1)
    rb = meta["in_bits"]
    half = 1 << (23 - rb - 1)
    rcodes = jnp.clip(jax.lax.shift_right_logical(mant + half, 23 - rb),
                      0, (1 << rb) - 1)
    rtab = lut(rcodes).astype(jnp.float32)
    return rtab * (2.0 ** -(rb + 1)) * pow2(-expo)


def _flash_loop(q, chunk, nk: int, block_k: int, out_ref, lut_exp,
                lut_recip, exp_meta: dict, recip_meta: dict, mask_chunk,
                chunk_live):
    """The online-softmax flash recurrence shared by the per-table,
    library-bound and absorbed-latent kernels: kv-chunked
    score/renormalize/PV loop with `pl.when`-style liveness skipping, then
    the reciprocal epilogue.

    ``chunk(start)`` returns one kv chunk's (BQ, BK) float32 scores and its
    (BK, Dv) values; ``mask_chunk(j, s)`` masks the scores of chunk ``j``
    (or returns them untouched); ``chunk_live(j)`` returns a traced
    liveness bool for the ``lax.cond`` skip, or None to always run the
    chunk. One copy of the m/l/acc update — the kernel variants differ
    only in their score, masking and table-read closures and cannot
    drift."""
    bq = q.shape[0]

    def body(j, carry):
        m_i, l_i, acc = carry
        s, vb = chunk(pl.multiple_of(j * block_k, block_k))
        s = mask_chunk(j, s)
        m_new = jnp.maximum(jnp.maximum(m_i, jnp.max(s, -1, keepdims=True)),
                            M_FLOOR)
        p = _table_exp_neg((m_new - s) * LOG2E, lut_exp, exp_meta)
        corr = _table_exp_neg((m_new - m_i) * LOG2E, lut_exp, exp_meta)
        l_new = l_i * corr + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(vb.dtype), vb,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    def guarded(j, carry):
        live = chunk_live(j)
        if live is None:
            return body(j, carry)
        return jax.lax.cond(live, lambda c: body(j, c), lambda c: c, carry)

    init = (jnp.full((bq, 1), M_FLOOR, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, out_ref.shape[-1]), jnp.float32))
    m_i, l_i, acc = jax.lax.fori_loop(0, nk, guarded, init)
    recip = _table_recip(jnp.maximum(l_i, 1e-30), lut_recip, recip_meta)
    out_ref[0] = (acc * recip).astype(out_ref.dtype)


def _scores(q, k):
    """(BQ, D) x (BK, D) -> (BQ, BK) float32 scores."""
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kv_chunk(q, k_ref, v_ref, block_k: int):
    """``_flash_loop``'s chunk closure over separate K and V stripes."""
    def chunk(start):
        kb = k_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)  # (BK, D)
        vb = v_ref[0, pl.ds(start, block_k), :]
        return _scores(q, kb), vb

    return chunk


def _flash_kernel(q_ref, k_ref, v_ref, ecoef_ref, rcoef_ref, out_ref, *,
                  causal: bool, scale: float, exp_meta: dict,
                  recip_meta: dict, block_k: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    bq = q.shape[0]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def mask_chunk(j, s):
        if not causal:
            return s
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        return jnp.where(q_pos >= k_pos, s, NEG)

    def chunk_live(j):
        if not causal:
            return None
        # B1 inside the kernel: skip chunks strictly above the diagonal
        return (j * block_k) <= (qi * bq + bq - 1)

    _flash_loop(q, _kv_chunk(q, k_ref, v_ref, block_k),
                k_ref.shape[1] // block_k, block_k, out_ref,
                lambda c: _lut(c, ecoef_ref, **exp_meta["eval"]),
                lambda c: _lut(c, rcoef_ref, **recip_meta["eval"]),
                exp_meta, recip_meta, mask_chunk, chunk_live)


def _positional(qp, kpos_ref, causal: bool, window: int | None):
    """The library kernels' masking and chunk-liveness closures, from
    *absolute* positions: ``qp`` the (BQ, 1) query column (-1 = padded
    row), ``kpos_ref`` one (1, BK) row of key positions per kv chunk (-1 =
    dead cache slot). Decode against a partially-filled cache masks dead
    slots, applies causality by position (not buffer index) and honors a
    sliding window — the contract of ``models.attention._mask``."""
    imax = jnp.iinfo(jnp.int32).max

    def kpos(j):
        return kpos_ref[0, pl.ds(j, 1), :]  # (1, BK)

    def mask_chunk(j, s):
        kpb = kpos(j)
        ok = kpb >= 0
        if causal:
            ok = jnp.logical_and(ok, qp >= kpb)
        if window is not None:
            ok = jnp.logical_and(ok, qp - kpb < window)
        return jnp.where(ok, s, NEG)

    def chunk_live(j):
        # chunk liveness from the position operands (the per-table kernel's
        # B1 by grid index can't see cache occupancy): dead if every slot is
        # empty, entirely in the causal future, or outside the window
        kpb = kpos(j)
        need = jnp.max(kpb) >= 0
        if causal:
            need = jnp.logical_and(
                need, jnp.min(jnp.where(kpb < 0, imax, kpb)) <= jnp.max(qp))
        if window is not None:
            qmin = jnp.min(jnp.where(qp < 0, imax, qp))
            need = jnp.logical_and(need, jnp.max(kpb) > qmin - window)
        return need

    return mask_chunk, chunk_live


def _lib_loop(q, chunk, qpos_ref, kpos_ref, rom_ref, out_ref, *, causal,
              window, r_max, exp_meta, recip_meta, block_k):
    """``_flash_loop`` with the library ROM's exp and recip reads and the
    position-operand masks."""
    mask_chunk, chunk_live = _positional(qpos_ref[0], kpos_ref, causal,
                                         window)
    _flash_loop(q, chunk, kpos_ref.shape[1], block_k, out_ref,
                lambda c: _lut_rom(c, rom_ref, fid=exp_meta["fid"],
                                   r_max=r_max, **exp_meta["eval"]),
                lambda c: _lut_rom(c, rom_ref, fid=recip_meta["fid"],
                                   r_max=r_max, **recip_meta["eval"]),
                exp_meta, recip_meta, mask_chunk, chunk_live)


def _flash_lib_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, rom_ref,
                      out_ref, *, scale: float, block_k: int, **kw):
    """Library-bound flash attention with explicit position operands.

    Both transcendentals read the whole-library ROM (`_lut_rom` at their
    static func ids) — the approximation datapath is inlined into the
    attention kernel, not a lookup service between ops. Query positions
    arrive as a (BQ, 1) column and key positions as one (1, BK) row per kv
    chunk, the layouts the (8, 128) tiling rule accepts (``_positional``).
    """
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    _lib_loop(q, _kv_chunk(q, k_ref, v_ref, block_k), qpos_ref, kpos_ref,
              rom_ref, out_ref, block_k=block_k, **kw)


def _mla_flash_lib_kernel(q_ref, qr_ref, c_ref, kr_ref, qpos_ref, kpos_ref,
                          rom_ref, out_ref, *, scale: float, block_k: int,
                          **kw):
    """Absorbed-latent (MLA) flash attention: the keys come in two parts,
    the latent stripe ``c_ref`` (Sk, Dc) and the shared rotary stripe
    ``kr_ref`` (Sk, Dr), and the values are the latent stripe itself, so a
    program reads each once. Scores are q_lat . c + q_rope . kr; the rest
    is ``_flash_lib_kernel``'s recurrence."""
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, Dc)
    qr = qr_ref[0].astype(jnp.float32) * scale  # (BQ, Dr)

    def chunk(start):
        cb = c_ref[0, pl.ds(start, block_k), :]  # (BK, Dc), also the values
        krb = kr_ref[0, pl.ds(start, block_k), :].astype(jnp.float32)
        return (_scores(q, cb.astype(jnp.float32)) + _scores(qr, krb)), cb

    _lib_loop(q, chunk, qpos_ref, kpos_ref, rom_ref, out_ref,
              block_k=block_k, **kw)


def _lib_call(body, name: str, qs: tuple, kvs: tuple, q_pos, kv_pos, rom,
              exp_meta: dict, recip_meta: dict, *, dv: int, dtype, r_max: int,
              causal: bool, window: int | None, scale: float, kv_group: int,
              block_q: int, block_k: int, interpret: bool | None):
    """One ``pallas_call`` of a library-bound flash body: ``qs`` are (N, Sq,
    *) query operands (one tile of ``block_q`` rows a program), ``kvs``
    (N // kv_group, Sk, *) kv stripes, read whole by query program i at
    stripe ``i // kv_group``, then the positions and the ROM."""
    n, sq, _ = qs[0].shape
    sk = kvs[0].shape[1]
    g = kv_group
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    assert n % g == 0 and all(a.shape[0] == n // g for a in kvs), \
        (n, g, [a.shape for a in kvs])
    assert q_pos.shape == (n, sq) and kv_pos.shape == (n // g, sk), \
        (q_pos.shape, kv_pos.shape)
    nk = sk // block_k
    kernel = functools.partial(body, causal=causal, window=window,
                               scale=scale, r_max=r_max, exp_meta=exp_meta,
                               recip_meta=recip_meta, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(n, sq // block_q),
        in_specs=[
            *(pl.BlockSpec((1, block_q, a.shape[-1]), lambda i, j: (i, j, 0))
              for a in qs),
            *(pl.BlockSpec((1, sk, a.shape[-1]), lambda i, j: (i // g, 0, 0))
              for a in kvs),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, nk, block_k), lambda i, j: (i // g, 0, 0)),
            rom_spec(),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, dv), dtype),
        interpret=interpret_mode(interpret),
        name=name,
    )(*qs, *kvs, q_pos.astype(jnp.int32).reshape(n, sq, 1),
      kv_pos.astype(jnp.int32).reshape(n // g, nk, block_k), flat_rom(rom))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    exp_coeffs: jax.Array, recip_coeffs: jax.Array,
                    exp_meta: dict, recip_meta: dict, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool | None = None) -> jax.Array:
    """q: (N, Sq, D); k, v: (N, Sk, D). N = batch x heads (GQA expansion is
    the caller's contract). Sq % block_q == 0, Sk % block_k == 0."""
    n, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    scale = (d ** -0.5) if scale is None else scale
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               exp_meta=exp_meta, recip_meta=recip_meta,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(n, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            rom_spec(),
            rom_spec(),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, d), v.dtype),
        interpret=interpret_mode(interpret),
        name="flash",
    )(q, k, v, flat_rom(exp_coeffs), flat_rom(recip_coeffs))


def flash_attention_lib(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_pos: jax.Array, kv_pos: jax.Array, rom: jax.Array,
                        exp_meta: dict, recip_meta: dict, *, r_max: int,
                        causal: bool = True, window: int | None = None,
                        scale: float | None = None, kv_group: int = 1,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                        interpret: bool | None = None) -> jax.Array:
    """q: (N, Sq, D); k: (N // kv_group, Sk, Dk); v: (N // kv_group, Sk,
    Dv); q_pos: (N, Sq) int32 (-1 = padded row); kv_pos: (N // kv_group,
    Sk) int32 (-1 = dead cache slot); rom: the library ROM as (F * r_max,
    3). N = batch x query heads; GQA is expressed through ``kv_group`` =
    heads per kv head — query program i reads kv stripe ``i // kv_group``
    via the BlockSpec index map, so grouped K/V are never materialized per
    query head. Sq % block_q == 0, Sk % block_k == 0.
    """
    d = q.shape[-1]
    return _lib_call(_flash_lib_kernel, "flash_lib", (q,), (k, v), q_pos,
                     kv_pos, rom, exp_meta, recip_meta, dv=v.shape[-1],
                     dtype=v.dtype, r_max=r_max, causal=causal,
                     window=window,
                     scale=(d ** -0.5) if scale is None else scale,
                     kv_group=kv_group, block_q=block_q, block_k=block_k,
                     interpret=interpret)


def flash_attention_mla_lib(q: jax.Array, q_rope: jax.Array, c: jax.Array,
                            k_rope: jax.Array, q_pos: jax.Array,
                            kv_pos: jax.Array, rom: jax.Array,
                            exp_meta: dict, recip_meta: dict, *, r_max: int,
                            scale: float, causal: bool = True,
                            window: int | None = None, kv_group: int = 1,
                            block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                            interpret: bool | None = None) -> jax.Array:
    """Absorbed-latent attention (``mla_flash_lib``): q: (N, Sq, Dc) queries
    taken into the latent space; q_rope: (N, Sq, Dr) their rotary part; c:
    (N // kv_group, Sk, Dc) the latent cache, keys and values at once;
    k_rope: (N // kv_group, Sk, Dr) the shared rotary key. Returns (N, Sq,
    Dc) latent outputs. ``scale`` is explicit: the published head width,
    not Dc + Dr, sets it. Positions, ROM, grid and tiles as in
    ``flash_attention_lib``."""
    return _lib_call(_mla_flash_lib_kernel, "mla_flash_lib", (q, q_rope),
                     (c, k_rope), q_pos, kv_pos, rom, exp_meta, recip_meta,
                     dv=c.shape[-1], dtype=c.dtype, r_max=r_max,
                     causal=causal, window=window, scale=scale,
                     kv_group=kv_group, block_q=block_q, block_k=block_k,
                     interpret=interpret)
