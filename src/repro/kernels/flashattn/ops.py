"""Jitted public wrappers for the table-numerics flash-attention kernels
(per-table designs, or the whole-library ROM with explicit positions)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.table import TableDesign
from repro.kernels.flashattn.kernel import (BLOCK_Q, flash_attention,
                                            flash_attention_lib,
                                            flash_attention_mla_lib)
from repro.kernels.flashattn.ref import (flash_attention_lib_ref,
                                         flash_attention_ref)
from repro.kernels.softmax.ops import _meta, lib_meta
from repro.launch.sharding import local_map, rule_spec
from repro.numerics.ops import ATTN_FOLD_KEY, note_attention_site
from repro.api import get_table


def _block(n: int) -> int:
    """Largest power-of-two tile in [8, 128] dividing n (n % 8 == 0)."""
    for b in (128, 64, 32, 16):
        if n % b == 0:
            return b
    return 8


def _folds(sq: int, h: int, kvh: int) -> bool:
    """A group's query rows fit one tile: one program per kv stripe takes
    all of them (decode, short chunks) instead of one per query head, each
    re-walking the same stripe. The group is a GQA group, or all of an
    absorbed MLA layer's heads over its one latent stripe."""
    g = h // kvh
    return g > 1 and sq * g <= BLOCK_Q


def _to_rows(q, q_pos, kvh: int, fold: bool):
    """(B, Sq, H, D) queries -> (N, R, D) program rows and their (N, R)
    positions. Folded: N = B * kvh and R = Sq * g, row s * g + i being
    query head kv * g + i at query s (at Sq = 1 a pure reshape); else
    N = B * H and R = Sq."""
    b, sq, h, d = q.shape
    qp = q_pos.astype(jnp.int32)
    if not fold:
        return (q.transpose(0, 2, 1, 3).reshape(b * h, sq, d),
                jnp.repeat(qp, h, axis=0))
    g = h // kvh
    qn = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 1, 3, 4)
    return (qn.reshape(b * kvh, sq * g, d),
            jnp.repeat(jnp.repeat(qp, g, axis=1), kvh, axis=0))


def _from_rows(o, b: int, sq: int, h: int, kvh: int, fold: bool):
    """Inverse of ``_to_rows`` on the (N, R, Dv) output."""
    dv = o.shape[-1]
    if not fold:
        return o.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
    o = o.reshape(b, kvh, sq, h // kvh, dv).transpose(0, 2, 1, 3, 4)
    return o.reshape(b, sq, h, dv)


def attention_fused_library(q: jax.Array, k: jax.Array, v: jax.Array,
                            library, *, causal: bool = True,
                            scale: float | None = None,
                            window: int | None = None,
                            q_pos: jax.Array | None = None,
                            kv_pos: jax.Array | None = None,
                            q_rope: jax.Array | None = None,
                            k_rope: jax.Array | None = None,
                            use_kernel: bool | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """(B, Sq, H, D) attention through the library-bound fused kernel.

    The library ROM is the single table operand (exp + recip read at their
    static func ids in-kernel). ``q_pos`` / ``kv_pos``: (B, S*) absolute
    positions (-1 = dead KV slot), the decode-against-cache contract of
    ``models.attention.attention_core``; ``None`` means the training layout
    (``arange``). GQA passes k/v with their own (fewer) heads, never
    materialized per query head; Dk may differ from Dv (expanded MLA).
    When a group's Sq * g query rows fit one tile (decode), they are folded
    into the rows of one program per kv stripe (counted under
    ``ATTN_FOLD_KEY``); otherwise each query-head program maps onto its kv
    stripe by index (prefill buckets). Absorbed MLA decode passes
    ``q_rope`` (B, Sq, H, Dr) and ``k_rope`` (B, Sk, KV, Dr), a second
    score term, with ``v is k``: the latent stripe is keys and values at
    once, read once a program (``mla_flash_lib``), and ``scale`` is
    explicit. ``use_kernel=None`` picks the Pallas kernel on TPU and the
    unchunked jnp oracle elsewhere; the kernel path pads rows and Sk to
    tile multiples with masked (-1) positions, and on a mesh runs per
    device on its batch rows and kv-head groups (``local_map``).
    """
    b, sq, h, d = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    assert h % kvh == 0, (h, kvh)
    latent = k_rope is not None
    if latent and (v is not k or q_rope is None or scale is None):
        raise ValueError("absorbed attention takes q_rope, k_rope and an "
                         "explicit scale, and its values are its keys (v is "
                         "k)")
    em, rm = lib_meta(library, "exp2neg"), lib_meta(library, "recip")
    fold = _folds(sq, h, kvh)
    if fold:
        note_attention_site(ATTN_FOLD_KEY)
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        # the unchunked oracle takes one kv stripe per program
        n = kvh if fold else h

        def stripes(a):
            return jnp.repeat(a.transpose(0, 2, 1, 3), n // kvh, axis=1
                              ).reshape(b * n, sk, a.shape[-1])

        qn, qp = _to_rows(q, q_pos, kvh, fold)
        kn = stripes(k)
        kp = jnp.repeat(kv_pos.astype(jnp.int32), n, axis=0)
        rope = {}
        if latent:
            rope = dict(q_rope=_to_rows(q_rope, q_pos, kvh, fold)[0],
                        k_rope=stripes(k_rope))
        o = flash_attention_lib_ref(qn, kn, kn if latent else stripes(v), qp,
                                    kp, library.coeffs, em, rm,
                                    causal=causal, window=window,
                                    scale=scale, **rope)
        return _from_rows(o, b, sq, h, kvh, fold)

    def kernel(q, k, v, q_pos, kv_pos, coeffs, *rope):
        b, _, h, _ = q.shape
        kvh = k.shape[2]

        def stripes(a):
            return a.transpose(0, 2, 1, 3).reshape(b * kvh, sk, a.shape[-1])

        qn, qp = _to_rows(q, q_pos, kvh, fold)
        if rope:  # (query, its rotary part) rows over (latent, rotary key)
            qs = [qn, _to_rows(rope[0], q_pos, kvh, fold)[0]]
            kvs = [stripes(k), stripes(rope[1])]
        else:
            qs, kvs = [qn], [stripes(k), stripes(v)]
        kp = jnp.repeat(kv_pos.astype(jnp.int32), kvh, axis=0)
        rows = qn.shape[1]
        pad_q, pad_k = (-rows) % 8, (-sk) % 8
        if pad_q:
            qs = [jnp.pad(a, ((0, 0), (0, pad_q), (0, 0))) for a in qs]
            qp = jnp.pad(qp, ((0, 0), (0, pad_q)), constant_values=-1)
        if pad_k:
            kvs = [jnp.pad(a, ((0, 0), (0, pad_k), (0, 0))) for a in kvs]
            kp = jnp.pad(kp, ((0, 0), (0, pad_k)), constant_values=-1)
        call = flash_attention_mla_lib if rope else flash_attention_lib
        o = call(*qs, *kvs, qp, kp, coeffs.reshape(-1, 3), em, rm,
                 r_max=coeffs.shape[1], causal=causal, window=window,
                 scale=scale, kv_group=1 if fold else h // kvh,
                 block_q=rows + pad_q if fold else _block(rows + pad_q),
                 block_k=_block(sk + pad_k), interpret=interpret)
        return _from_rows(o[:, :rows], b, sq, h, kvh, fold)

    # on a mesh: batch rows and whole kv-head groups are independent, so
    # query heads shard exactly where their kv heads do
    kv_spec = rule_spec(("batch", None, "kv_heads", None), k.shape)
    bax, hax = (kv_spec[0], kv_spec[2]) if len(kv_spec) == 4 else (None, None)
    q_spec, pos_spec = P(bax, None, hax, None), P(bax, None)
    rope = (q_rope, k_rope) if latent else ()
    return local_map(kernel, (q, k, v, q_pos, kv_pos, library.coeffs, *rope),
                     (q_spec, q_spec, q_spec, pos_spec, pos_spec, P(),
                      *(q_spec for _ in rope)),
                     q_spec)


def attention_fused(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    exp_design: TableDesign | None = None,
                    recip_design: TableDesign | None = None,
                    use_kernel: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """(B, S, H, D) multi-head attention through the fused kernel.

    GQA callers expand kv heads first (kernel contract: one kv stripe per
    query head)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    assert k.shape[2] == h, "expand GQA kv heads before calling"
    exp_design = exp_design or get_table("exp2neg")
    recip_design = recip_design or get_table("recip")
    qn = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kn = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vn = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    if not use_kernel:
        o = flash_attention_ref(qn, kn, vn, exp_design, recip_design,
                                causal=causal, scale=scale)
    else:
        ec = exp_design.device_coeffs(checked=True)
        rc = recip_design.device_coeffs(checked=True)
        o = flash_attention(qn, kn, vn, ec, rc, _meta(exp_design),
                            _meta(recip_design), causal=causal, scale=scale,
                            interpret=interpret)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
