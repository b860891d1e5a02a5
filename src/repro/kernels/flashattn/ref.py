"""Pure-jnp oracle for the flash-attention kernel: unchunked attention with
the same table-backed exponential / reciprocal semantics."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.numerics.ops import approx_exp_neg, approx_recip_pos

NEG = -1e30
M_FLOOR = -1e20
LOG2E = 1.4426950408889634


def flash_attention_lib_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                            q_pos: jax.Array, kv_pos: jax.Array,
                            coeffs: jax.Array, exp_meta: dict,
                            recip_meta: dict, *, causal: bool = True,
                            window: int | None = None,
                            scale: float | None = None,
                            q_rope: jax.Array | None = None,
                            k_rope: jax.Array | None = None) -> jax.Array:
    """Unchunked oracle of the library-bound flash kernel.

    Same in-kernel glue (`_table_exp_neg` / `_table_recip`) over the padded
    (F, R_max, 3) ROM — the integer table reads are bit-identical to the
    kernel's `_lut_rom`; only the chunked renormalization order differs.
    q: (N, Sq, D); k: (N, Sk, Dk); v: (N, Sk, Dv); positions as in the
    kernel (-1 = dead/padded row). ``q_rope`` (N, Sq, Dr) / ``k_rope`` (N,
    Sk, Dr): the absorbed-latent kernel's second score term."""
    from repro.kernels.flashattn.kernel import _table_exp_neg, _table_recip
    from repro.kernels.interp.ref import interp_eval_ref
    from repro.kernels.softmax.ref import _rom_rows

    def rom_lut(meta):
        rows = _rom_rows(coeffs, meta)
        seg = meta["eval"].get("seg")
        if seg is not None:  # ROM v2 slot: segment-index datapath
            from repro.kernels.interp.ref import interp_eval_seg_ref

            return lambda c: interp_eval_seg_ref(c, rows, seg=seg)
        return lambda c: interp_eval_ref(c, rows, **meta["eval"])

    n, sq, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    if k_rope is not None:
        s = s + jnp.einsum("nqd,nkd->nqk", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))
    s = s * scale
    ok = (kv_pos >= 0)[:, None, :]
    if causal:
        ok = jnp.logical_and(ok, q_pos[:, :, None] >= kv_pos[:, None, :])
    if window is not None:
        ok = jnp.logical_and(ok, q_pos[:, :, None] - kv_pos[:, None, :] < window)
    s = jnp.where(ok, s, NEG)
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), M_FLOOR)
    p = _table_exp_neg((m - s) * LOG2E, rom_lut(exp_meta), exp_meta)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("nqk,nkd->nqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    recip = _table_recip(jnp.maximum(l, 1e-30), rom_lut(recip_meta), recip_meta)
    return (o * recip).astype(v.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        exp_design, recip_design, *, causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """q: (N, Sq, D); k, v: (N, Sk, D)."""
    n, sq, d = q.shape
    sk = k.shape[1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qp = jnp.arange(sq)[:, None]
        kp = jnp.arange(sk)[None, :]
        s = jnp.where(qp >= kp, s, NEG)
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), M_FLOOR)
    p = approx_exp_neg(s - m, exp_design)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("nqk,nkd->nqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o * approx_recip_pos(jnp.maximum(l, 1e-30), recip_design)
            ).astype(v.dtype)
