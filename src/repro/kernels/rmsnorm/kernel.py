"""Pallas TPU kernel: fused RMSNorm with a table-backed rsqrt.

mean-square -> rsqrt via the generated table over [1, 4) (IEEE exponent
split, odd/even-exponent segment select) -> scale by gamma. One (rows, D)
pass; the rsqrt LUT is the paper-generated artifact. Features off the
128-lane grid are zero-padded by the caller; ``d_valid`` keeps the mean
over the real features.
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


def _rmsnorm_body(x, gamma, lut, meta: dict, eps: float, out_dtype,
                  d_valid: int | None = None):
    """Fused RMSNorm math over an abstract in-kernel rsqrt table read (per-
    table ``_lut`` or library-ROM ``_lut_rom`` closure); one copy of the
    float glue shared by both kernel variants."""
    x = x.astype(jnp.float32)  # (BLOCK_ROWS, D)
    d = x.shape[-1] if d_valid is None else d_valid
    ms = jnp.sum(x * x, axis=-1, keepdims=True) / d + eps  # > 0
    bits = jax.lax.bitcast_convert_type(ms, jnp.int32)
    e = jnp.bitwise_and(jax.lax.shift_right_logical(bits, 23), 255) - 127
    mant = jnp.bitwise_and(bits, (1 << 23) - 1)
    b = meta["in_bits"]
    halfcode = 1 << (b - 1)
    rnd = 1 << (23 - (b - 1) - 1)
    frac_code = jnp.clip(jax.lax.shift_right_logical(mant + rnd, 23 - (b - 1)),
                         0, halfcode - 1)
    even = jnp.bitwise_and(e, 1) == 0  # e even -> v = 1.mant in [1,2): segment 0
    codes = jnp.where(even, frac_code, halfcode + frac_code)
    # floor(e / 2) and floor((e - 1) / 2) as arithmetic shifts
    h = jax.lax.shift_right_arithmetic(jnp.where(even, e, e - 1), 1)
    tab = lut(codes.astype(jnp.int32)).astype(jnp.float32)
    rs = tab * (2.0 ** -meta["out_bits"]) * pow2(-h)
    return (x * rs * gamma.astype(jnp.float32)).astype(out_dtype)


def _rmsnorm_kernel(x_ref, gamma_ref, coef_ref, out_ref, *, meta: dict, eps: float):
    out_ref[...] = _rmsnorm_body(
        x_ref[...], gamma_ref[...],
        lambda c: _lut(c, coef_ref, **meta["eval"]),
        meta, eps, out_ref.dtype)


def _rmsnorm_lib_kernel(x_ref, gamma_ref, rom_ref, out_ref, *, r_max: int,
                        meta: dict, eps: float, d_valid: int | None):
    """Library-bound fused RMSNorm: the rsqrt read is a `_lut_rom` select
    at its static func id against the whole-library ROM operand."""
    out_ref[...] = _rmsnorm_body(
        x_ref[...], gamma_ref[...],
        lambda c: _lut_rom(c, rom_ref, fid=meta["fid"], r_max=r_max,
                           **meta["eval"]),
        meta, eps, out_ref.dtype, d_valid)


def _row_call(kernel, x: jax.Array, gamma: jax.Array, rom: jax.Array,
              interpret: bool | None, name: str):
    rows, d = x.shape
    assert rows % BLOCK_ROWS == 0 and d % 128 == 0, x.shape
    block = pl.BlockSpec((BLOCK_ROWS, d), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[block, pl.BlockSpec((1, d), lambda i: (0, 0)), rom_spec()],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret_mode(interpret),
        name=name,
    )(x, gamma.reshape(1, d), flat_rom(rom))


def fused_rmsnorm_lib(x: jax.Array, gamma: jax.Array, rom: jax.Array,
                      meta: dict, *, r_max: int, eps: float = 1e-6,
                      d_valid: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """x: (rows, D), rows % BLOCK_ROWS == 0, D % 128 == 0 (lanes past
    ``d_valid`` are zero padding); rom: library coefficient ROM as (F *
    r_max, 3) int32."""
    kernel = functools.partial(_rmsnorm_lib_kernel, r_max=r_max, meta=meta,
                               eps=eps, d_valid=d_valid)
    return _row_call(kernel, x, gamma, rom, interpret, "rmsnorm_lib")


def fused_rmsnorm(x: jax.Array, gamma: jax.Array, coeffs: jax.Array, meta: dict,
                  eps: float = 1e-6, interpret: bool | None = None) -> jax.Array:
    kernel = functools.partial(_rmsnorm_kernel, meta=meta, eps=eps)
    return _row_call(kernel, x, gamma, coeffs, interpret, "rmsnorm")
