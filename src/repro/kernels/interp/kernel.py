"""Pallas TPU kernel: batched piecewise-polynomial table evaluation.

This is the TPU rendering of the paper's Figure-1 datapath:

  * the coefficient ROM lives in SMEM, flattened row-major to int32
    scalars (``(2^R, 3)`` -> ``(3 * 2^R,)``; a whole library is a few KiB);
  * the LUT read is a select-accumulate over the ROM rows on the VPU — the
    software form of the ROM mux tree: for each row, every lane whose index
    matches takes that row's scalars. It is exact for any int32 coefficient
    (no MXU pass, no float rounding) and needs no reshape of the (8, 128)
    tile, both of which Mosaic rejects for the one-hot contraction;
  * the squarer operates on the truncated ``x[W-1:i]`` exactly like the RTL;
  * evaluation is int32 throughout, final arithmetic shift by k.

Tiling: input codes are reshaped to (rows, 128) lanes; the grid walks row
blocks of 8, so each program touches an (8, 128) VREG-aligned tile while the
whole ROM stays resident in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCK_ROWS = 8
LANES = 128
_UNROLL_ROWS = 64  # ROM reads over at most this many rows unroll fully


def pow2(n: jax.Array) -> jax.Array:
    """``2.0 ** n`` (float32) for integer-valued float ``n``, built from the
    exponent bits: exact on every backend — ``exp2`` is not (XLA's CPU
    lowering is off by ulps at integer arguments and flushes 2^-126 to 0).
    ``n`` is clamped to the normal range [-126, 127]. The table glue's
    power-of-two scalings all go through this one helper, in the kernels
    and in their jnp oracles alike."""
    e = jnp.clip(n.astype(jnp.int32) + 127, 1, 254)
    return jax.lax.bitcast_convert_type(jax.lax.shift_left(e, 23),
                                        jnp.float32)


def rom_spec() -> pl.BlockSpec:
    """BlockSpec of a ROM-side operand: the whole flattened int32 array,
    resident in SMEM for every grid step."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def flat_rom(rom: jax.Array) -> jax.Array:
    """A ROM-side operand as the kernels take it: 1-D int32, row-major."""
    return rom.reshape(-1).astype(jnp.int32)


def rom_read(idx: jax.Array, rom_ref, *, n: int, width: int = 3,
             base: int = 0) -> tuple[jax.Array, ...]:
    """In-kernel ROM read: ``width`` int32 arrays shaped like ``idx`` with
    ``out[c][e] = rom_ref[base + idx[e] * width + c]`` for ``idx[e]`` in
    ``[0, n)`` and 0 elsewhere — the one-hot contraction's semantics, as a
    VPU select over the ``n`` rows (scalar SMEM loads broadcast per row)."""

    def row(r, acc):
        hit = idx == r
        off = base + r * width
        return tuple(jnp.where(hit, rom_ref[off + c], a)
                     for c, a in enumerate(acc))

    acc = (jnp.zeros(idx.shape, jnp.int32),) * width
    if n <= _UNROLL_ROWS:  # one table slot: static rows, static offsets
        for r in range(n):
            acc = row(r, acc)
        return acc
    # a whole library: a loop over groups of rows (Mosaic unrolls a loop
    # fully or not at all, so the group is unrolled by hand)
    u = 8 if n % 8 == 0 else 1

    def group(i, acc):
        for t in range(u):
            acc = row(i * u + t, acc)
        return acc

    return jax.lax.fori_loop(0, n // u, group, acc)


def poly_tail(c0: jax.Array, c1: jax.Array, c2: jax.Array, x: jax.Array, *,
              k, sq_trunc, lin_trunc, degree) -> jax.Array:
    """The Figure-1 fixed-point tail shared by every in-kernel table read:
    truncated square/linear terms, int32 Horner accumulate, arithmetic
    shift by k. The datapath constants are Python ints (one static table)
    or per-element int32 arrays (library reads, where each element names
    its own function or leaf); one copy serves both, so no two reads can
    drift."""
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, sq_trunc), sq_trunc)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, lin_trunc),
                            lin_trunc)
    acc = c1 * xl + c2
    if not isinstance(degree, int):
        xs = jnp.where(degree == 2, xs, 0)  # degree-1 rows skip the squarer
        acc = acc + c0 * xs * xs
    elif degree == 2:
        acc = acc + c0 * xs * xs
    return jax.lax.shift_right_arithmetic(acc, k)


def _lut(codes: jax.Array, rom_ref, *, eval_bits: int, k: int,
         sq_trunc: int, lin_trunc: int, degree: int, row0: int = 0,
         n_rows: int | None = None) -> jax.Array:
    """Uniform table evaluation on int32 codes (any 2-D shape): region
    index from the code's top bits, a ROM read of rows ``[row0, row0 +
    n_rows)`` (default: the whole ROM), then the shared fixed-point
    tail."""
    if n_rows is None:
        n_rows = rom_ref.shape[0] // 3 - row0
    r = jax.lax.shift_right_logical(codes, eval_bits)
    x = jnp.bitwise_and(codes, (1 << eval_bits) - 1)
    c0, c1, c2 = rom_read(r, rom_ref, n=n_rows, base=3 * row0)
    return poly_tail(c0, c1, c2, x, k=k, sq_trunc=sq_trunc,
                     lin_trunc=lin_trunc, degree=degree)


def _lut_seg(codes: jax.Array, rom_ref, *, row0: int, seg: tuple) -> jax.Array:
    """Non-uniform (ROM v2) slot evaluation: segment-index read, then the
    per-leaf fixed-point tail.

    The slot starts at ROM row ``row0``: rows ``[0, S)`` hold the S
    per-leaf coefficient triples and rows ``[S, S + ceil(2^D/3))`` the
    segment-index table packed 3 int32 entries per row — row-major, so
    entry ``c`` is flat word ``3 * (row0 + S) + c``. ``seg`` is the static
    ``FuncMeta.seg_spec()`` tuple ``(in_bits, depth, n_leaves,
    leaf_meta)`` with one ``(eval_bits, k, sq_trunc, lin_trunc, degree)``
    row per leaf — this is the address decoder the paper's uniform layout
    avoids: the top D input bits index a 2^D table that names the leaf, and
    the leaf supplies both the coefficient row and the datapath constants.
    The leaf constants are scalar literals selected per element (a
    materialized meta matrix would be a captured constant, which Pallas
    rejects). Degenerate segmentations (every leaf at depth R) reproduce
    the uniform ``_lut`` bitwise: the cell index equals the region index,
    every leaf row carries the uniform datapath constants, and the int32
    accumulate is order-insensitive (wrapping adds commute).
    """
    in_bits, depth, n_leaves, leaf_meta = seg
    cell = jax.lax.shift_right_logical(codes, in_bits - depth)
    (leaf,) = rom_read(cell, rom_ref, n=1 << depth, width=1,
                       base=3 * (row0 + n_leaves))

    def pick(j: int) -> jax.Array:
        acc = jnp.zeros(codes.shape, jnp.int32)
        for i in range(n_leaves):
            acc = jnp.where(leaf == i, leaf_meta[i][j], acc)
        return acc

    eb, k, sq, lin, deg = (pick(j) for j in range(5))
    x = jnp.bitwise_and(codes, jax.lax.shift_left(jnp.int32(1), eb) - 1)
    c0, c1, c2 = rom_read(leaf, rom_ref, n=n_leaves, base=3 * row0)
    return poly_tail(c0, c1, c2, x, k=k, sq_trunc=sq, lin_trunc=lin,
                     degree=deg)


def _lut_rom(codes: jax.Array, rom_ref, *, fid: int, r_max: int,
             eval_bits: int, k: int, sq_trunc: int, lin_trunc: int,
             degree: int, seg: tuple | None = None) -> jax.Array:
    """Table evaluation against a library ROM (static function id).

    ``rom_ref`` is an :class:`repro.api.InterpLibrary` coefficient ROM
    flattened to ``(F * r_max * 3,)`` int32; rows ``[fid * r_max, fid *
    r_max + 2^R)`` hold the function's ``packed_coeffs`` and the padding
    rows are zero. ``fid``/``r_max`` are static, so the read selects over
    the function's r_max rows only, and is exactly ``_lut`` on them —
    bit-identical to the per-table kernels. The consuming fused kernels
    (softmax / rmsnorm / flashattn) thread the whole library ROM as ONE
    operand and evaluate each transcendental in-registers instead of
    launching a standalone table kernel between ops.

    ``seg`` (a static ``FuncMeta.seg_spec()`` tuple) switches the slot to
    the non-uniform ROM-v2 datapath: the per-call eval_bits/k/truncation
    scalars are ignored (each leaf carries its own) and the rows decode
    through :func:`_lut_seg` instead of :func:`_lut`.
    """
    if seg is not None:
        return _lut_seg(codes, rom_ref, row0=fid * r_max, seg=seg)
    return _lut(codes, rom_ref, eval_bits=eval_bits, k=k, sq_trunc=sq_trunc,
                lin_trunc=lin_trunc, degree=degree, row0=fid * r_max,
                n_rows=r_max)


def _rom_kernel(codes_ref, rom_ref, out_ref, **lut_kw):
    out_ref[...] = _lut_rom(codes_ref[...], rom_ref, **lut_kw)


def _tiled_call(kernel, tiles: tuple, roms: tuple,
                interpret: bool | None, name: str) -> jax.Array:
    """Run ``kernel`` over (rows, 128) int32 tile operands in (8, 128)
    blocks with every ROM-side operand whole in SMEM; ``name`` names the
    kernel in the compiled program (and the trace)."""
    rows, lanes = tiles[0].shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, tiles[0].shape
    assert all(t.shape == tiles[0].shape for t in tiles), \
        [t.shape for t in tiles]
    tile = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[tile] * len(tiles) + [rom_spec()] * len(roms),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret_mode(interpret),
        name=name,
    )(*tiles, *(flat_rom(r) for r in roms))


def rom_eval_2d(codes: jax.Array, rom: jax.Array, *, fid: int, r_max: int,
                eval_bits: int, k: int, sq_trunc: int, lin_trunc: int,
                degree: int, seg: tuple | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Golden-test harness for ``_lut_rom``: evaluate one function of a
    ``(F * r_max, 3)`` library ROM on (rows, 128) codes through the same
    in-kernel datapath the fused consumers use."""
    kernel = functools.partial(_rom_kernel, fid=fid, r_max=r_max,
                               eval_bits=eval_bits, k=k, sq_trunc=sq_trunc,
                               lin_trunc=lin_trunc, degree=degree, seg=seg)
    return _tiled_call(kernel, (codes,), (rom,), interpret, "rom_eval")


def _library_kernel(codes_ref, fids_ref, rom_ref, meta_ref, out_ref, *,
                    n_funcs: int, r_max: int):
    """Fused multi-function table evaluation: read by (func_id, region).

    ``rom_ref`` is the library's padded ROM flattened to ``(n_funcs *
    r_max * 3,)``; ``meta_ref`` the per-function datapath ``(n_funcs * 5,)``:
    eval_bits, k, sq_trunc, lin_trunc, degree. Both reads are ROM selects
    like the single-table kernel; the shifts take per-element amounts,
    which Mosaic lowers as vector shifts.
    """
    codes = codes_ref[...]  # (BLOCK_ROWS, LANES) int32
    fids = fids_ref[...]
    eb, k, sq, lin, deg = rom_read(fids, meta_ref, n=n_funcs, width=5)
    r = jax.lax.shift_right_logical(codes, eb)
    x = jnp.bitwise_and(codes, jax.lax.shift_left(jnp.int32(1), eb) - 1)
    # fused ROM read: row index = func_id * r_max + region
    c0, c1, c2 = rom_read(fids * r_max + r, rom_ref, n=n_funcs * r_max)
    out_ref[...] = poly_tail(c0, c1, c2, x, k=k, sq_trunc=sq, lin_trunc=lin,
                             degree=deg)


def library_eval_2d(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                    meta: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """codes/fids: (rows, 128) int32, rows % 8 == 0; coeffs: (F, R_max, 3);
    meta: (F, 5) int32 rows of (eval_bits, k, sq_trunc, lin_trunc, degree)."""
    n_funcs, r_max, _ = coeffs.shape
    kernel = functools.partial(_library_kernel, n_funcs=n_funcs, r_max=r_max)
    return _tiled_call(kernel, (codes, fids), (coeffs, meta), interpret,
                       "_library_eval")


def _library_walk_kernel(codes_ref, fids_ref, rom_ref, walk_ref, dp_ref,
                         out_ref, *, n_funcs: int, r_max: int, n_dp: int):
    """Generalized multi-function ROM walk: uniform (v1) and segmented
    (v2) slots in one program.

    Per function, ``walk_ref`` carries ``(in_bits, depth, seg_flag,
    leaf_base, n_leaves)``: depth is R for a uniform slot and the
    segment-index depth D for a segmented one, so ``cell = code >>
    (in_bits - depth)`` is the region index (uniform) or the prefix-tree
    cell (segmented). A segmented element resolves the cell to a leaf id
    through the packed segment-index table — whose entries are row-major
    in the flattened ROM, so entry index ``(fid*r_max + n_leaves)*3 +
    cell`` needs no integer division by the 3-per-row packing — while a
    uniform element's leaf IS its cell. The coefficient row is then
    ``fid*r_max + leaf`` for both layouts, and the per-element datapath
    constants come from ``dp_ref`` at ``leaf_base (+ leaf)``: one row
    per uniform function, one per segmented leaf. Every read is a ROM
    select and the fixed-point tail is the same vector-shift datapath as
    ``_library_kernel``/``_lut_seg``, so each slot evaluates
    bit-identically to its specialized path.

    Unlike ``_lut_seg`` (whose leaf meta must fold into the jaxpr as
    scalar literals), the walk and datapath tables here are real kernel
    operands — the per-function layout varies, so it must be data.
    """
    codes = codes_ref[...]  # (BLOCK_ROWS, LANES) int32
    fids = fids_ref[...]
    in_b, depth, segf, lbase, nlv = rom_read(fids, walk_ref, n=n_funcs,
                                             width=5)
    cell = jax.lax.shift_right_logical(codes, in_b - depth)
    # segment-index read (garbage for uniform elements, masked below)
    (leaf_seg,) = rom_read((fids * r_max + nlv) * 3 + cell, rom_ref,
                           n=n_funcs * r_max * 3, width=1)
    leaf = jnp.where(segf == 1, leaf_seg, cell)
    # coefficient read: row = fid * r_max + leaf for both layouts
    c0, c1, c2 = rom_read(fids * r_max + leaf, rom_ref, n=n_funcs * r_max)
    # per-element datapath constants
    eb, k, sq, lin, deg = rom_read(lbase + jnp.where(segf == 1, leaf, 0),
                                   dp_ref, n=n_dp, width=5)
    x = jnp.bitwise_and(codes, jax.lax.shift_left(jnp.int32(1), eb) - 1)
    out_ref[...] = poly_tail(c0, c1, c2, x, k=k, sq_trunc=sq, lin_trunc=lin,
                             degree=deg)


def library_walk_2d(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                    walk: jax.Array, dp: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """codes/fids: (rows, 128) int32, rows % 8 == 0; coeffs: (F, R_max, 3);
    walk: (F, 5) int32 rows of (in_bits, depth, seg_flag, leaf_base,
    n_leaves); dp: (L, 5) int32 per-leaf datapath rows."""
    n_funcs, r_max, _ = coeffs.shape
    kernel = functools.partial(_library_walk_kernel, n_funcs=n_funcs,
                               r_max=r_max, n_dp=dp.shape[0])
    return _tiled_call(kernel, (codes, fids), (coeffs, walk, dp), interpret,
                       "_library_walk")


def _interp_kernel(codes_ref, rom_ref, out_ref, **lut_kw):
    out_ref[...] = _lut(codes_ref[...], rom_ref, **lut_kw)


def interp_eval_2d(codes: jax.Array, coeffs: jax.Array, *, eval_bits: int,
                   k: int, sq_trunc: int, lin_trunc: int, degree: int,
                   interpret: bool | None = None) -> jax.Array:
    """codes: (rows, 128) int32, rows % 8 == 0; coeffs: (2^R, 3) int32."""
    kernel = functools.partial(_interp_kernel, eval_bits=eval_bits, k=k,
                               sq_trunc=sq_trunc, lin_trunc=lin_trunc,
                               degree=degree)
    return _tiled_call(kernel, (codes,), (coeffs,), interpret,
                       "interp_eval")
