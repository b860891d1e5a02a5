"""Pallas TPU kernel: fused softmax with table-backed exp + reciprocal.

The paper's generated "hardware" evaluated inside one fused pass:

  1. row max (VPU reduction), t = (max - x) * log2(e) >= 0
  2. exponential: 2^-t = 2^-n * table_exp(frac(t))   — LUT + poly datapath
  3. row sum, then 1/sum via IEEE-754 exponent/mantissa split feeding the
     reciprocal table over [1, 2)                     — second LUT datapath
  4. scale.

The mantissa split uses integer bit twiddles (bitcast) exactly like the RTL
front-end the paper's reciprocal assumes (input already normalized to 1.x).
Table reads are SMEM ROM selects; see kernels/interp for rationale.
Tiling: (BLOCK_ROWS, D) blocks, the whole feature dim resident in VMEM.
Features off the 128-lane grid are padded by the caller; ``d_valid`` masks
the pad lanes out of the max and the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.interp.kernel import (_lut, _lut_rom, flat_rom, pow2,
                                         rom_spec)

BLOCK_ROWS = 8
LOG2E = 1.4426950408889634


def _softmax_body(x, lut_exp, lut_recip, exp_meta: dict, recip_meta: dict,
                  out_dtype, d_valid: int | None = None):
    """Fused softmax math, parameterized over the two in-kernel table reads.

    ``lut_exp`` / ``lut_recip`` map int32 codes to the table's integer
    output — either a per-table ``_lut`` or a library-ROM ``_lut_rom``
    closure. Exactly one implementation of the float glue exists, so the
    per-table and library-bound kernels cannot drift. ``d_valid`` (static)
    is the real feature count when the lane dim carries padding."""
    x = x.astype(jnp.float32)  # (BLOCK_ROWS, D)
    valid = None
    if d_valid is not None and d_valid < x.shape[-1]:
        valid = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d_valid
        x = jnp.where(valid, x, -jnp.inf)
    m = jnp.max(x, axis=-1, keepdims=True)
    t = jnp.minimum((m - x) * LOG2E, 126.0)
    n = jnp.floor(t)
    frac = t - n
    eb = exp_meta["in_bits"]
    codes = jnp.clip(jnp.round(frac * (1 << eb)).astype(jnp.int32), 0, (1 << eb) - 1)
    tab = lut_exp(codes).astype(jnp.float32)
    e = tab * (2.0 ** -exp_meta["out_bits"]) * pow2(-n)
    if valid is not None:
        e = jnp.where(valid, e, 0.0)
    s = jnp.sum(e, axis=-1, keepdims=True)  # > 0
    # IEEE-754 split: s = 1.mant * 2^(E-127); reciprocal table wants 1.x codes
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    expo = jnp.bitwise_and(jax.lax.shift_right_logical(bits, 23), 255) - 127
    mant = jnp.bitwise_and(bits, (1 << 23) - 1)
    rb = recip_meta["in_bits"]
    half = 1 << (23 - rb - 1)
    rcodes = jnp.clip(jax.lax.shift_right_logical(mant + half, 23 - rb),
                      0, (1 << rb) - 1)
    rtab = lut_recip(rcodes).astype(jnp.float32)
    recip = rtab * (2.0 ** -(rb + 1)) * pow2(-expo)
    return (e * recip).astype(out_dtype)


def _softmax_kernel(x_ref, ecoef_ref, rcoef_ref, out_ref, *, exp_meta: dict,
                    recip_meta: dict):
    out_ref[...] = _softmax_body(
        x_ref[...],
        lambda c: _lut(c, ecoef_ref, **exp_meta["eval"]),
        lambda c: _lut(c, rcoef_ref, **recip_meta["eval"]),
        exp_meta, recip_meta, out_ref.dtype)


def _softmax_lib_kernel(x_ref, rom_ref, out_ref, *, r_max: int,
                        exp_meta: dict, recip_meta: dict,
                        d_valid: int | None):
    """Library-bound fused softmax: ONE ROM operand for both tables; the
    exp and recip reads are `_lut_rom` selects at their static func ids —
    the whole softmax (including both transcendentals) is a single kernel
    with no intermediate HBM round-trip."""
    out_ref[...] = _softmax_body(
        x_ref[...],
        lambda c: _lut_rom(c, rom_ref, fid=exp_meta["fid"], r_max=r_max,
                           **exp_meta["eval"]),
        lambda c: _lut_rom(c, rom_ref, fid=recip_meta["fid"], r_max=r_max,
                           **recip_meta["eval"]),
        exp_meta, recip_meta, out_ref.dtype, d_valid)


def _row_call(kernel, x: jax.Array, roms: tuple, interpret: bool | None,
              name: str):
    """(BLOCK_ROWS, D) row blocks of ``x`` with every ROM whole in SMEM;
    ``name`` names the kernel in the compiled program (and the trace)."""
    rows, d = x.shape
    assert rows % BLOCK_ROWS == 0 and d % 128 == 0, x.shape
    block = pl.BlockSpec((BLOCK_ROWS, d), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[block] + [rom_spec()] * len(roms),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret_mode(interpret),
        name=name,
    )(x, *(flat_rom(r) for r in roms))


def fused_softmax_lib(x: jax.Array, rom: jax.Array, exp_meta: dict,
                      recip_meta: dict, *, r_max: int,
                      d_valid: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """x: (rows, D) with rows % BLOCK_ROWS == 0, D % 128 == 0 (lanes past
    ``d_valid`` are padding); rom: the library coefficient ROM as (F *
    r_max, 3) int32."""
    kernel = functools.partial(_softmax_lib_kernel, r_max=r_max,
                               exp_meta=exp_meta, recip_meta=recip_meta,
                               d_valid=d_valid)
    return _row_call(kernel, x, (rom,), interpret, "softmax_lib")


def fused_softmax(x: jax.Array, exp_coeffs: jax.Array, recip_coeffs: jax.Array,
                  exp_meta: dict, recip_meta: dict,
                  interpret: bool | None = None) -> jax.Array:
    """x: (rows, D) with rows % BLOCK_ROWS == 0, D % 128 == 0."""
    kernel = functools.partial(_softmax_kernel, exp_meta=exp_meta,
                               recip_meta=recip_meta)
    return _row_call(kernel, x, (exp_coeffs, recip_coeffs), interpret,
                     "softmax")
