"""Pure-jnp oracle for the interp kernel (gather semantics, exact ints)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def library_eval_ref(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                     meta: jax.Array) -> jax.Array:
    """Gather-semantics oracle for the fused multi-function kernel.

    coeffs: (F, R_max, 3) int32; meta: (F, 5) int32 rows of
    (eval_bits, k, sq_trunc, lin_trunc, degree). Bit-identical to running
    each element through ``interp_eval_ref`` with its own table.
    """
    m = meta[fids]  # (..., 5)
    eb, k, sq, lin, deg = (m[..., i] for i in range(5))
    one = jnp.int32(1)
    r = jax.lax.shift_right_logical(codes, eb)
    x = jnp.bitwise_and(codes, jax.lax.shift_left(one, eb) - 1)
    sel = coeffs[fids, r]  # (..., 3)
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq), sq)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin), lin)
    xs = jnp.where(deg == 2, xs, 0)
    acc = sel[..., 0] * xs * xs + sel[..., 1] * xl + sel[..., 2]
    return jax.lax.shift_right_arithmetic(acc, k)


def library_walk_ref(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                     walk: jax.Array, dp: jax.Array) -> jax.Array:
    """Gather-semantics oracle for the generalized multi-function ROM walk
    (uniform v1 + segmented v2 slots in one call).

    coeffs: (F, R_max, 3) int32; walk: (F, 5) int32 rows of (in_bits,
    depth, seg_flag, leaf_base, n_leaves); dp: (L, 5) int32 per-leaf
    (eval_bits, k, sq_trunc, lin_trunc, degree) rows — one per uniform
    function, one per segmented leaf. Bit-identical per slot to
    ``library_eval_ref`` (uniform) and ``interp_eval_seg_ref``
    (segmented).
    """
    codes = codes.astype(jnp.int32)
    f, r_max, _ = coeffs.shape
    rom = coeffs.reshape(f * r_max, 3)
    w = walk[fids]  # (..., 5)
    in_b, depth, segf, lbase, nlv = (w[..., i] for i in range(5))
    cell = jax.lax.shift_right_logical(codes, in_b - depth)
    # the packed segment-index table's entries are row-major in the
    # flattened ROM: entry index = (fid*r_max + n_leaves)*3 + cell.
    # Uniform elements read garbage here (clamped in bounds) and mask it.
    entries = rom.reshape(-1)
    eidx = (fids * r_max + nlv) * 3 + cell
    leaf_seg = entries[jnp.clip(eidx, 0, entries.shape[0] - 1)]
    leaf = jnp.where(segf == 1, leaf_seg, cell)
    sel = rom[fids * r_max + leaf]  # (..., 3)
    m = dp[lbase + jnp.where(segf == 1, leaf, 0)]  # (..., 5)
    eb, k, sq, lin, deg = (m[..., i] for i in range(5))
    one = jnp.int32(1)
    x = jnp.bitwise_and(codes, jax.lax.shift_left(one, eb) - 1)
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq), sq)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin), lin)
    xs = jnp.where(deg == 2, xs, 0)
    acc = sel[..., 0] * xs * xs + sel[..., 1] * xl + sel[..., 2]
    return jax.lax.shift_right_arithmetic(acc, k)


def interp_eval_seg_ref(codes: jax.Array, rows: jax.Array, *,
                        seg: tuple) -> jax.Array:
    """Gather-semantics oracle for the non-uniform (ROM v2) slot datapath.

    ``rows`` is one function's slot: ``[0, S)`` per-leaf coefficient
    triples, then the segment-index table packed 3 int32 per row. ``seg``
    is the static ``FuncMeta.seg_spec()`` tuple ``(in_bits, depth,
    n_leaves, leaf_meta)``. Bit-identical to the in-kernel ``_lut_seg``
    ROM-select path (tests/kernels) and to ``SegmentedDesign.eval_int``.
    """
    in_bits, depth, n_leaves, leaf_meta = seg
    n_cells = 1 << depth
    n_table_rows = (n_cells + 2) // 3
    seg_tab = rows[n_leaves:n_leaves + n_table_rows].reshape(-1)[:n_cells]
    codes = codes.astype(jnp.int32)
    cell = jax.lax.shift_right_logical(codes, in_bits - depth)
    leaf = seg_tab[cell]
    m = jnp.asarray(leaf_meta, jnp.int32)[leaf]  # (..., 5)
    eb, k, sq, lin, deg = (m[..., i] for i in range(5))
    one = jnp.int32(1)
    x = jnp.bitwise_and(codes, jax.lax.shift_left(one, eb) - 1)
    sel = rows[:n_leaves][leaf]  # (..., 3)
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq), sq)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin), lin)
    xs = jnp.where(deg == 2, xs, 0)
    acc = sel[..., 0] * xs * xs + sel[..., 1] * xl + sel[..., 2]
    return jax.lax.shift_right_arithmetic(acc, k)


def interp_eval_ref(codes: jax.Array, coeffs: jax.Array, *, eval_bits: int,
                    k: int, sq_trunc: int, lin_trunc: int, degree: int) -> jax.Array:
    r = jax.lax.shift_right_logical(codes, eval_bits)
    x = jnp.bitwise_and(codes, (1 << eval_bits) - 1)
    sel = coeffs[r]
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq_trunc), sq_trunc)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin_trunc), lin_trunc)
    acc = sel[..., 1] * xl + sel[..., 2]
    if degree == 2:
        acc = acc + sel[..., 0] * xs * xs
    return jax.lax.shift_right_arithmetic(acc, k)


# ---------------------------------------------------------------------------
# Emulated-int64 ("wide") exact evaluation — DESIGN.md §7.5's fallback for
# designs whose coefficients exceed int32 (e.g. wide-output reciprocals).
# jax runs with x64 disabled, so a literal jnp.int64 path would silently
# downcast; instead every 64-bit value is a (hi, lo) pair of 32-bit words
# and all arithmetic is exact modulo 2^64 — which equals the true signed
# result because ``TableDesign.eval_int`` (the numpy oracle) already
# guarantees the accumulator fits int64.
# ---------------------------------------------------------------------------


def _u32(x: jax.Array) -> jax.Array:
    """Reinterpret an int32 bit pattern as uint32 (no value conversion)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)


def _i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _umul32(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Full 64-bit product of two uint32 arrays -> (hi, lo) uint32 words."""
    mask = jnp.uint32(0xFFFF)
    a0, a1 = a & mask, a >> 16
    b0, b1 = b & mask, b >> 16
    p00, p11 = a0 * b0, a1 * b1
    mid = a0 * b1 + a1 * b0  # may wrap: reconstruct the carry below
    carry_mid = (mid < a0 * b1).astype(jnp.uint32)
    lo = p00 + (mid << 16)
    carry_lo = (lo < p00).astype(jnp.uint32)
    hi = p11 + (mid >> 16) + (carry_mid << 16) + carry_lo
    return hi, lo


def _add64(ah, al, bh, bl) -> tuple[jax.Array, jax.Array]:
    lo = al + bl
    hi = ah + bh + (lo < al).astype(jnp.uint32)
    return hi, lo


def _mul64_64(ah, al, bh, bl) -> tuple[jax.Array, jax.Array]:
    """Low 64 bits of a 64x64-bit product (exact when the true signed
    product fits int64; two's-complement multiplication mod 2^64 equals the
    signed product mod 2^64, so no sign correction is needed)."""
    hi, lo = _umul32(al, bl)
    hi = hi + al * bh + ah * bl  # cross terms: only their low words survive
    return hi, lo


def _shra64(h: jax.Array, l: jax.Array, k: int) -> jax.Array:
    """Arithmetic >> k (static, 0 <= k <= 63) of (hi, lo); returns the low
    word of the result as int32 — the design contract keeps post-shift
    outputs within out_bits < 32."""
    if k == 0:
        return _i32(l)
    hs = _i32(h)
    if k < 32:
        return _i32((l >> k) | (h << (32 - k)))
    return jax.lax.shift_right_arithmetic(hs, min(k - 32, 31))


def interp_eval_wide(codes: jax.Array, coeffs_wide: jax.Array, *,
                     eval_bits: int, k: int, sq_trunc: int, lin_trunc: int,
                     degree: int) -> jax.Array:
    """Exact table evaluation with 64-bit coefficients, x64-off safe.

    ``coeffs_wide``: (2^R, 3, 2) int32 — ``[..., 0]`` the high and
    ``[..., 1]`` the low word of each int64 coefficient (two's complement,
    ``TableDesign.device_coeffs_wide``). Bit-identical to the numpy
    ``TableDesign.eval_int`` for any design whose accumulator fits int64,
    which the exhaustive ``verify`` sweep already presumes.
    """
    codes = codes.astype(jnp.int32)
    r = jax.lax.shift_right_logical(codes, eval_bits)
    x = jnp.bitwise_and(codes, (1 << eval_bits) - 1)
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq_trunc), sq_trunc)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin_trunc), lin_trunc)
    sel = coeffs_wide[r]  # (..., 3, 2)
    zero = jnp.zeros_like(_u32(x))
    # b * lin(x): 64 x 32 (x >= 0, so its high word is zero)
    acc = _mul64_64(_u32(sel[..., 1, 0]), _u32(sel[..., 1, 1]), zero, _u32(xl))
    acc = _add64(*acc, _u32(sel[..., 2, 0]), _u32(sel[..., 2, 1]))
    if degree == 2:
        sq = _umul32(_u32(xs), _u32(xs))  # sq(x)^2 may itself exceed int32
        acc = _add64(*acc, *_mul64_64(_u32(sel[..., 0, 0]),
                                      _u32(sel[..., 0, 1]), *sq))
    return _shra64(*acc, k)
