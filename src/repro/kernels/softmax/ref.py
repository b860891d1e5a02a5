"""Oracle for the fused softmax kernel: identical math in plain jnp."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.interp.kernel import pow2

LOG2E = 1.4426950408889634


def _rom_rows(coeffs, meta: dict):
    """Slice one function's live rows out of a padded (F, R_max, 3) ROM."""
    seg = meta["eval"].get("seg")
    if seg is not None:  # ROM v2 slot: per-leaf coeffs + packed seg table
        _, depth, n_leaves, _ = seg
        n_rows = n_leaves + ((1 << depth) + 2) // 3
    else:
        n_rows = 1 << (meta["in_bits"] - meta["eval"]["eval_bits"])
    return coeffs[meta["fid"], :n_rows]


def fused_softmax_lib_ref(x, coeffs, exp_meta, recip_meta):
    """jnp oracle of the library-bound fused softmax kernel: gather the two
    functions' rows from the padded ROM, then the identical glue — bit-
    identical to the per-table oracle because the padded ROM holds exactly
    ``packed_coeffs`` in rows [0, 2^R)."""
    return fused_softmax_ref(x, _rom_rows(coeffs, exp_meta),
                             _rom_rows(coeffs, recip_meta), exp_meta,
                             recip_meta)


def fused_softmax_ref(x, exp_coeffs, recip_coeffs, exp_meta, recip_meta):
    def lut(codes, coeffs, eval_bits, k, sq_trunc, lin_trunc, degree,
            seg=None):
        if seg is not None:
            from repro.kernels.interp.ref import interp_eval_seg_ref

            return interp_eval_seg_ref(codes, coeffs, seg=seg)
        r = jax.lax.shift_right_logical(codes, eval_bits)
        xi = jnp.bitwise_and(codes, (1 << eval_bits) - 1)
        sel = coeffs[r]
        xs = jax.lax.shift_left(jax.lax.shift_right_logical(xi, sq_trunc), sq_trunc)
        xl = jax.lax.shift_left(jax.lax.shift_right_logical(xi, lin_trunc), lin_trunc)
        acc = sel[..., 1] * xl + sel[..., 2]
        if degree == 2:
            acc = acc + sel[..., 0] * xs * xs
        return jax.lax.shift_right_arithmetic(acc, k)

    xf = x.astype(jnp.float32)
    m = jnp.max(xf, axis=-1, keepdims=True)
    t = jnp.minimum((m - xf) * LOG2E, 126.0)
    n = jnp.floor(t)
    frac = t - n
    eb = exp_meta["in_bits"]
    codes = jnp.clip(jnp.round(frac * (1 << eb)).astype(jnp.int32), 0, (1 << eb) - 1)
    tab = lut(codes, exp_coeffs, **exp_meta["eval"]).astype(jnp.float32)
    e = tab * (2.0 ** -exp_meta["out_bits"]) * pow2(-n)
    s = jnp.sum(e, axis=-1, keepdims=True)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    expo = jnp.bitwise_and(jax.lax.shift_right_logical(bits, 23), 255) - 127
    mant = jnp.bitwise_and(bits, (1 << 23) - 1)
    rb = recip_meta["in_bits"]
    half = 1 << (23 - rb - 1)
    rcodes = jnp.clip(jax.lax.shift_right_logical(mant + half, 23 - rb),
                      0, (1 << rb) - 1)
    rtab = lut(rcodes, recip_coeffs, **recip_meta["eval"]).astype(jnp.float32)
    recip = rtab * (2.0 ** -(rb + 1)) * pow2(-expo)
    return (e * recip).astype(x.dtype)
