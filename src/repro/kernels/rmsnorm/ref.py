"""Oracle for the fused rmsnorm kernel (identical math, plain jnp gather)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.interp.kernel import pow2


def fused_rmsnorm_lib_ref(x, gamma, coeffs, meta, eps=1e-6):
    """jnp oracle of the library-bound fused RMSNorm kernel: slice the rsqrt
    rows out of the padded (F, R_max, 3) ROM, then the identical glue."""
    from repro.kernels.softmax.ref import _rom_rows

    return fused_rmsnorm_ref(x, gamma, _rom_rows(coeffs, meta), meta, eps)


def fused_rmsnorm_ref(x, gamma, coeffs, meta, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    bits = jax.lax.bitcast_convert_type(ms, jnp.int32)
    e = jnp.bitwise_and(jax.lax.shift_right_logical(bits, 23), 255) - 127
    mant = jnp.bitwise_and(bits, (1 << 23) - 1)
    b = meta["in_bits"]
    halfcode = 1 << (b - 1)
    rnd = 1 << (23 - (b - 1) - 1)
    frac_code = jnp.clip(jax.lax.shift_right_logical(mant + rnd, 23 - (b - 1)),
                         0, halfcode - 1)
    even = jnp.bitwise_and(e, 1) == 0
    codes = jnp.where(even, frac_code, halfcode + frac_code).astype(jnp.int32)
    h = jnp.where(even, e // 2, (e - 1) // 2)
    ev = meta["eval"]
    if ev.get("seg") is not None:  # ROM v2 slot: segment-index datapath
        from repro.kernels.interp.ref import interp_eval_seg_ref

        tab = interp_eval_seg_ref(codes, coeffs,
                                  seg=ev["seg"]).astype(jnp.float32)
    else:
        r = jax.lax.shift_right_logical(codes, ev["eval_bits"])
        xi = jnp.bitwise_and(codes, (1 << ev["eval_bits"]) - 1)
        sel = coeffs[r]
        xs = jax.lax.shift_left(
            jax.lax.shift_right_logical(xi, ev["sq_trunc"]), ev["sq_trunc"])
        xl = jax.lax.shift_left(
            jax.lax.shift_right_logical(xi, ev["lin_trunc"]), ev["lin_trunc"])
        acc = sel[..., 1] * xl + sel[..., 2]
        if ev["degree"] == 2:
            acc = acc + sel[..., 0] * xs * xs
        tab = jax.lax.shift_right_arithmetic(acc, ev["k"]).astype(jnp.float32)
    rs = tab * (2.0 ** -meta["out_bits"]) * pow2(-h)
    return (xf * rs * gamma.astype(jnp.float32)).astype(x.dtype)
