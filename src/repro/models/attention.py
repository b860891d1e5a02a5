"""Attention: GQA (+ sliding window), MLA, cross-attention, and a blockwise
(flash-style) core that keeps 32k-prefill activation footprints bounded.

The numerics backend is threaded through every softmax so the paper's
table-based exponential/reciprocal can replace the XLA transcendentals
(``cfg.numerics = "interp"``).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.launch.sharding import constrain
from repro.numerics.ops import ATTN_ABSORB_KEY, note_attention_site
from repro.models.layers import Params, ShapeTree, apply_rope, pdtype, rope_angles, spec

NEG = -1e30
M_FLOOR = -1e20  # running-max clamp: exp(NEG - M_FLOOR) == 0 without a
                 # second mask-select on the (B,KV,G,Q,S) prob block
                 # (perf iteration B1, EXPERIMENTS.md §Perf)


class KVCache(NamedTuple):
    k: jax.Array  # (B, KV, S, D)  [MLA: (B, S, kv_lora); k holds compressed]
    v: jax.Array  # (B, KV, S, D)  [MLA: (B, S, rope_dim) shared rope key]
    pos: jax.Array  # (B, S) int32 positions held in each slot, -1 = empty


# ---------------------------------------------------------------------------
# blockwise softmax(QK^T)V with running renormalization
# ---------------------------------------------------------------------------

def _mask(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """(B, Tq, Tk) bool validity mask."""
    d = q_pos[:, :, None] - kv_pos[:, None, :]
    ok = kv_pos[:, None, :] >= 0
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return ok


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, kv_pos: jax.Array, numerics,
                   causal: bool = True, window: Optional[int] = None,
                   q_chunk: int = 1024, kv_chunk: int = 1024,
                   softmax_scale: float | None = None,
                   q_rope: jax.Array | None = None,
                   k_rope: jax.Array | None = None) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Sk,KV,Dk/Dv); *_pos: (B, S*) int32.

    Grouped heads are expressed as (KV, G) so the head contraction matches
    the GQA weight sharding; chunked over both Sq and Sk with flash-style
    renormalization (all exponentials/reciprocals via the numerics backend).
    ``q_rope`` (B,Sq,H,Dr) / ``k_rope`` (B,Sk,KV,Dr) add a second score
    term q_rope . k_rope, so a key held in two parts (absorbed MLA: latent
    and shared rotary key) is never concatenated; that form counts one
    ``ATTN_ABSORB_KEY`` site.
    """
    b, sq, h, d = q.shape
    _, sk, kvh, dk = k.shape
    dv = v.shape[-1]
    g = h // kvh
    if k_rope is not None:
        note_attention_site(ATTN_ABSORB_KEY)
    fused = getattr(numerics, "fused_attention", None)
    if fused is not None:
        # fused numerics inline the whole datapath (scores, table-backed
        # exp/recip, PV product) into one kernel; None = unsupported layout,
        # fall through to the chunked glue path
        out = fused(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                    scale=softmax_scale, q_rope=q_rope, k_rope=k_rope)
        if out is not None:
            return out
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    q = q.reshape(b, sq, kvh, g, d)
    qr = None if q_rope is None else q_rope.reshape(b, sq, kvh, g, -1)

    def scores(qb, kb, qrb, krb):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                       preferred_element_type=jnp.float32)
        if krb is not None:
            s = s + jnp.einsum("bqkgd,bskd->bkgqs", qrb, krb,
                               preferred_element_type=jnp.float32)
        return s * scale

    def _divisor_chunk(n: int, target: int) -> int:
        c = min(target, n)
        while n % c:
            c -= 1
        return c

    q_chunk = _divisor_chunk(sq, q_chunk)
    kv_chunk = _divisor_chunk(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk

    if nq == 1 and nk == 1:
        s = scores(q, k, qr, k_rope)
        m = _mask(q_pos, kv_pos, causal, window)[:, None, None]
        s = jnp.where(m, s, NEG)
        mx = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(s, -1, keepdims=True), M_FLOOR))
        p = numerics.exp_neg(s - mx)  # masked entries: exp(NEG - mx) == 0
        l = jnp.sum(p, -1, keepdims=True)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        o = o * numerics.recip_pos(l).transpose(0, 3, 1, 2, 4)
        return o.reshape(b, sq, h, dv).astype(v.dtype)

    kc = k.reshape(b, nk, kv_chunk, kvh, dk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, kv_chunk, kvh, dv).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(b, nk, kv_chunk).transpose(1, 0, 2)
    krc = (None if k_rope is None else k_rope.reshape(
        b, nk, kv_chunk, kvh, -1).transpose(1, 0, 2, 3, 4))

    def q_block(qb, qpb, qrb):
        # qb: (B, Tq, KV, G, D); qpb: (B, Tq); qrb: (B, Tq, KV, G, Dr) | None
        def compute_chunk(carry, kb, vb, kpb, krb, masked: bool):
            m_i, l_i, acc = carry
            s = scores(qb, kb, qrb, krb)
            if masked:  # only boundary chunks pay the mask-select (B2)
                msk = _mask(qpb, kpb, causal, window)[:, None, None]
                s = jnp.where(msk, s, NEG)
            m_new = jnp.maximum(
                jnp.maximum(m_i, jax.lax.stop_gradient(jnp.max(s, -1))),
                M_FLOOR)
            p = numerics.exp_neg(s - m_new[..., None])  # masked -> exp(NEG)=0
            corr = numerics.exp_neg(jnp.minimum(m_i - m_new, 0.0))
            l_new = l_i * corr + jnp.sum(p, -1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return m_new, l_new, acc_new

        # B1/B2 pay off when many chunks are skippable; below this the
        # lax.cond branches just break XLA fusion (measured: ~-15% memory
        # term on 4-chunk train cells, +2.3x on 32-chunk prefill cells)
        use_skip = nk >= 8

        def kv_step(carry, xs):
            kb, vb, kpb, krb = xs
            if not use_skip:
                return (compute_chunk(carry, kb, vb, kpb, krb, masked=True),
                        None)
            # chunk-level liveness (perf iteration B1): a kv chunk is dead if
            # it is entirely in the causal future of every query, entirely
            # outside the sliding window, or entirely empty cache slots.
            # lax.cond skips the matmuls at runtime (~2x for causal prefill).
            need = jnp.any(kpb >= 0)
            if causal:
                need &= jnp.min(jnp.where(kpb < 0, jnp.iinfo(jnp.int32).max,
                                          kpb)) <= jnp.max(qpb)
            if window is not None:
                need &= jnp.max(kpb) > jnp.min(qpb) - window
            # B2: interior chunks (entirely valid for every query) skip the
            # mask-select chain; only diagonal/window-boundary chunks pay it.
            full = jnp.all(kpb >= 0)
            if causal:
                full &= jnp.max(kpb) <= jnp.min(qpb)
            if window is not None:
                full &= jnp.min(kpb) > jnp.max(qpb) - window

            def live(c):
                return jax.lax.cond(
                    full,
                    lambda cc: compute_chunk(cc, kb, vb, kpb, krb,
                                             masked=False),
                    lambda cc: compute_chunk(cc, kb, vb, kpb, krb,
                                             masked=True),
                    c)

            carry = jax.lax.cond(need, live, lambda c: c, carry)
            return carry, None

        tq = qb.shape[1]
        init = (jnp.full((b, kvh, g, tq), M_FLOOR, jnp.float32),
                jnp.zeros((b, kvh, g, tq), jnp.float32),
                jnp.zeros((b, kvh, g, tq, dv), jnp.float32))
        (m_i, l_i, acc), _ = jax.lax.scan(kv_step, init, (kc, vc, pc, krc))
        o = acc * numerics.recip_pos(jnp.maximum(l_i, 1e-30))[..., None]
        return o.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, dv).astype(v.dtype)

    qs = q.reshape(b, nq, q_chunk, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    qps = q_pos.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    qrs = (None if qr is None else qr.reshape(
        b, nq, q_chunk, kvh, g, -1).transpose(1, 0, 2, 3, 4, 5))
    out = jax.lax.map(lambda xs: q_block(*xs), (qs, qps, qrs))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


# ---------------------------------------------------------------------------
# GQA (+ QKV bias, + sliding window)
# ---------------------------------------------------------------------------

def gqa_shapes(cfg) -> ShapeTree:
    d, hd, dt = cfg.d_model, cfg.head_size, pdtype(cfg)
    out = {
        "wq": spec((d, cfg.n_heads * hd), dt),
        "wk": spec((d, cfg.n_kv_heads * hd), dt),
        "wv": spec((d, cfg.n_kv_heads * hd), dt),
        "wo": spec((cfg.n_heads * hd, d), dt),
    }
    if cfg.attn_bias:
        out.update({
            "bq": spec((cfg.n_heads * hd,), dt),
            "bk": spec((cfg.n_kv_heads * hd,), dt),
            "bv": spec((cfg.n_kv_heads * hd,), dt),
        })
    return out


def _gqa_qkv(p: Params, x: jax.Array, positions: jax.Array, cfg):
    b, s, _ = x.shape
    hd = cfg.head_size
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if not cfg.learned_pos:
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "seq2", "heads", None))
    k = constrain(k, ("batch", "seq2", "kv_heads", None))
    v = constrain(v, ("batch", "seq2", "kv_heads", None))
    return q, k, v


def gqa_train(p: Params, x: jax.Array, positions: jax.Array, cfg, numerics,
              causal: bool = True) -> jax.Array:
    b, s, _ = x.shape
    q, k, v = _gqa_qkv(p, x, positions, cfg)
    o = attention_core(q, k, v, positions, positions, numerics,
                       causal=causal, window=cfg.sliding_window)
    o = constrain(o, ("batch", "seq2", "heads", None))
    # C3: sequence-parallel output — constraining the row-parallel matmul
    # result to the seq shard turns its partial-sum all-reduce into a
    # reduce-scatter (Megatron-SP), 16x less traffic and no full-seq f32
    # buffer in the scan body.
    return constrain(o.reshape(b, s, -1) @ p["wo"], ("batch", "seq", None))


def gqa_prefill(p: Params, x, positions, cfg, numerics, cache_len: int):
    """Training-shaped pass that also emits a right-padded KV cache."""
    b, s, _ = x.shape
    q, k, v = _gqa_qkv(p, x, positions, cfg)
    o = attention_core(q, k, v, positions, positions, numerics,
                       causal=True, window=cfg.sliding_window)
    y = o.reshape(b, s, -1) @ p["wo"]
    s_eff = (min(cache_len, cfg.sliding_window)
             if cfg.sliding_window is not None else cache_len)
    kc = jnp.zeros((b, cfg.n_kv_heads, s_eff, cfg.head_size), k.dtype)
    vc = jnp.zeros_like(kc)
    pos_buf = jnp.full((b, s_eff), -1, jnp.int32)
    if cfg.sliding_window is not None and s > s_eff:
        # windowed caches keep the last s_eff tokens; prompts overflowing a
        # non-windowed cache stay a hard (shape) error, never a silent clip
        k, v = k[:, -s_eff:], v[:, -s_eff:]
        positions = positions[:, -s_eff:]
    kc = jax.lax.dynamic_update_slice(kc, k.transpose(0, 2, 1, 3), (0, 0, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v.transpose(0, 2, 1, 3), (0, 0, 0, 0))
    pos_buf = jax.lax.dynamic_update_slice(pos_buf, positions.astype(jnp.int32), (0, 0))
    if cfg.sliding_window is not None and s > s_eff:
        # decode slots windowed rows at position % s_eff; rotate the
        # compacted tail so row r holds the position with p % s_eff == r —
        # otherwise the first wrap-around decode overwrites live in-window
        # KV instead of the expired row
        shift = s % s_eff
        kc = jnp.roll(kc, shift, axis=2)
        vc = jnp.roll(vc, shift, axis=2)
        pos_buf = jnp.roll(pos_buf, shift, axis=1)
    return y, KVCache(kc, vc, pos_buf)


def _decode_positions(pos: jax.Array, b: int) -> tuple[jax.Array, jax.Array]:
    """Normalize a decode position argument: scalar (uniform batch) or (B,)
    per-slot vector. Returns (pos, positions (B, 1))."""
    pos = jnp.asarray(pos, jnp.int32)
    positions = (jnp.broadcast_to(pos[None, None], (b, 1)) if pos.ndim == 0
                 else pos.reshape(b, 1)).astype(jnp.int32)
    return pos, positions


def _put_token(buf: jax.Array, new: jax.Array, slot: jax.Array,
               layer: jax.Array | None) -> jax.Array:
    """Write each batch row's new token into ``buf`` at ``slot`` along the
    position axis (the last axis of ``pos``, the second to last of K/V).

    ``new`` is one layer's (B, ...) rows, length 1 on that axis; ``slot``
    is () (uniform batch) or (B,) per-slot. Without ``layer``, ``buf`` is
    that layer's buffer; with it, ``buf`` is a scanned segment's stack (L,
    B, ...) and the token lands at [layer, b, ..., slot, ...] in place —
    the rest of the stack is neither read nor written."""
    new = new.astype(buf.dtype)
    t = buf.ndim - (1 if new.ndim == 2 else 2)  # the position axis
    if slot.ndim == 0:
        start = [0] * buf.ndim
        start[t] = slot
        if layer is not None:
            start[0], new = layer, new[None]
        return jax.lax.dynamic_update_slice(buf, new, start)
    if layer is None:
        # one dynamic_update per batch row (vmap lowers these to a batched
        # scatter)
        def one(b_buf, b_new, s):
            start = [0] * b_buf.ndim
            start[t - 1] = s
            return jax.lax.dynamic_update_slice(b_buf, b_new, start)

        return jax.vmap(one)(buf, new, slot)
    # one in-place row write per batch row into the stack
    for i in range(new.shape[0]):
        start = [layer, i] + [0] * (buf.ndim - 2)
        start[t] = slot[i]
        buf = jax.lax.dynamic_update_slice(buf, new[i][None, None], start)
    return buf


def _cache_write(cache: KVCache, new: KVCache, slot: jax.Array,
                 layer: jax.Array | None) -> tuple[KVCache, KVCache]:
    """Write the decode token's rows (``new``: K/V or latent/rotary rows,
    and the (B, 1) positions) into ``cache`` at ``slot``. Returns (the
    cache as written, the layer's view of it). With ``layer``, ``cache``
    is a scanned segment's layer stack carried through the layer loop:
    the rows land in place and the view is that layer's slice."""
    cache = KVCache(*(_put_token(buf, n, slot, layer)
                      for buf, n in zip(cache, new)))
    if layer is None:
        return cache, cache
    return cache, KVCache(*(jax.lax.dynamic_index_in_dim(
        buf, layer, 0, keepdims=False) for buf in cache))


def gqa_decode(p: Params, x: jax.Array, pos: jax.Array, cache: KVCache, cfg,
               numerics, layer: jax.Array | None = None
               ) -> tuple[jax.Array, KVCache]:
    """x: (B, 1, d); pos: scalar int32 (uniform across batch) or (B,)
    per-slot positions (mixed-length continuous batching). ``layer``:
    ``cache`` is the segment's layer stack and this is its layer ``layer``
    (see ``_cache_write``)."""
    b = x.shape[0]
    pos, positions = _decode_positions(pos, b)
    q, k, v = _gqa_qkv(p, x, positions, cfg)
    s_max = cache.k.shape[-2]
    slot = (pos % s_max).astype(jnp.int32) if cfg.sliding_window else pos
    cache, (kc, vc, kv_pos) = _cache_write(
        cache, KVCache(k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                       positions), slot, layer)
    o = attention_core(q, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3),
                       positions, kv_pos, numerics, causal=True,
                       window=cfg.sliding_window,
                       kv_chunk=min(4096, s_max))
    y = o.reshape(b, 1, -1) @ p["wo"]
    return y, cache


def gqa_cache_specs(cfg, b: int, s: int, dtype) -> KVCache:
    s_eff = min(s, cfg.sliding_window) if cfg.sliding_window else s
    return KVCache(
        k=spec((b, cfg.n_kv_heads, s_eff, cfg.head_size), dtype),
        v=spec((b, cfg.n_kv_heads, s_eff, cfg.head_size), dtype),
        pos=spec((b, s_eff), jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLA (DeepSeek/MiniCPM3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_shapes(cfg) -> ShapeTree:
    m, d, dt = cfg.mla, cfg.d_model, pdtype(cfg)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": spec((d, m.q_lora_rank), dt),
        "q_norm": {"scale": spec((m.q_lora_rank,), dt)},
        "wq_b": spec((m.q_lora_rank, cfg.n_heads * qk), dt),
        "wkv_a": spec((d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": {"scale": spec((m.kv_lora_rank,), dt)},
        "wkv_b": spec((m.kv_lora_rank, cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim)), dt),
        "wo": spec((cfg.n_heads * m.v_head_dim, d), dt),
    }


def _mla_q(p, x, positions, cfg, numerics):
    m = cfg.mla
    b, s, _ = x.shape
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ql = numerics.rmsnorm(x @ p["wq_a"], p["q_norm"]["scale"].astype(jnp.float32)).astype(x.dtype)
    q = (ql @ p["wq_b"]).reshape(b, s, cfg.n_heads, qk)
    qn, qr = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    qr = apply_rope(qr, cos, sin)
    return jnp.concatenate([qn, qr], -1)


def _mla_kv_latent(p, x, positions, cfg, numerics):
    m = cfg.mla
    kv = x @ p["wkv_a"]
    ckv, kr = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    ckv = numerics.rmsnorm(ckv, p["kv_norm"]["scale"].astype(jnp.float32)).astype(x.dtype)
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    kr = apply_rope(kr[:, :, None, :], cos, sin)[:, :, 0, :]
    return ckv, kr  # (B,S,kv_lora), (B,S,rope)


def _mla_expand(p, ckv, kr, cfg):
    """Latents -> per-head K (nope+rope) and V. Only prefill (and training)
    expands; decode attends over the latent cache (``mla_decode``)."""
    m = cfg.mla
    b, s, _ = ckv.shape
    kvb = (ckv @ p["wkv_b"]).reshape(b, s, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    kn, v = kvb[..., : m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    kr_b = jnp.broadcast_to(kr[:, :, None, :], (b, s, cfg.n_heads, m.qk_rope_head_dim))
    k = jnp.concatenate([kn, kr_b], -1)
    return k, v


def _mla_attend(p, x, positions, ckv, kr, cfg, numerics, causal: bool):
    """Expanded-form attention over the sequence's own latents."""
    b, s, _ = x.shape
    q = _mla_q(p, x, positions, cfg, numerics)
    k, v = _mla_expand(p, ckv, kr, cfg)
    q = constrain(q, ("batch", "seq2", "heads", None))
    k = constrain(k, ("batch", "seq2", "heads", None))
    o = attention_core(q, k, v, positions, positions, numerics, causal=causal)
    return constrain(o.reshape(b, s, -1) @ p["wo"], ("batch", "seq", None))  # C3


def mla_train(p: Params, x, positions, cfg, numerics, causal: bool = True):
    ckv, kr = _mla_kv_latent(p, x, positions, cfg, numerics)
    return _mla_attend(p, x, positions, ckv, kr, cfg, numerics, causal)


def mla_prefill(p, x, positions, cfg, numerics, cache_len: int):
    m = cfg.mla
    b, s, _ = x.shape
    ckv, kr = _mla_kv_latent(p, x, positions, cfg, numerics)
    y = _mla_attend(p, x, positions, ckv, kr, cfg, numerics, causal=True)
    ck_buf = jnp.zeros((b, cache_len, m.kv_lora_rank), ckv.dtype)
    kr_buf = jnp.zeros((b, cache_len, m.qk_rope_head_dim), kr.dtype)
    pos_buf = jnp.full((b, cache_len), -1, jnp.int32)
    ck_buf = jax.lax.dynamic_update_slice(ck_buf, ckv, (0, 0, 0))
    kr_buf = jax.lax.dynamic_update_slice(kr_buf, kr, (0, 0, 0))
    pos_buf = jax.lax.dynamic_update_slice(pos_buf, positions.astype(jnp.int32), (0, 0))
    return y, KVCache(ck_buf, kr_buf, pos_buf)


def mla_decode(p, x, pos, cache: KVCache, cfg, numerics, layer=None):
    """pos: scalar int32 or (B,) per-slot positions (continuous batching);
    ``layer``: as ``gqa_decode``'s.

    Absorbed form: ``wkv_b``'s key half takes the query's no-position part
    into the latent space and its value half brings the latent output back
    up, so every head attends to the cached latent and shared rotary key as
    they lie — one kv head, all heads its group, the two score terms
    summed, the latent as values — and the cache is never expanded,
    concatenated or transposed."""
    m = cfg.mla
    b = x.shape[0]
    pos, positions = _decode_positions(pos, b)
    q = _mla_q(p, x, positions, cfg, numerics)
    ckv, kr = _mla_kv_latent(p, x, positions, cfg, numerics)
    cache, (ck, krb, pc) = _cache_write(cache, KVCache(ckv, kr, positions),
                                        pos, layer)
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, cfg.n_heads,
                               m.qk_nope_head_dim + m.v_head_dim)
    w_k, w_v = wkv_b[..., : m.qk_nope_head_dim], wkv_b[..., m.qk_nope_head_dim:]
    q_lat = jnp.einsum("bshn,chn->bshc", q[..., : m.qk_nope_head_dim], w_k,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    lat = ck[:, :, None, :]  # (B, S, 1, kv_lora): keys and values
    o = attention_core(q_lat, lat, lat, positions, pc, numerics, causal=True,
                       kv_chunk=min(4096, ck.shape[1]),
                       softmax_scale=1.0 / math.sqrt(q.shape[-1]),
                       q_rope=q[..., m.qk_nope_head_dim:],
                       k_rope=krb[:, :, None, :])
    o = jnp.einsum("bshc,chv->bshv", o, w_v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = o.reshape(b, 1, -1) @ p["wo"]
    return y, cache


def mla_cache_specs(cfg, b: int, s: int, dtype) -> KVCache:
    m = cfg.mla
    return KVCache(
        k=spec((b, s, m.kv_lora_rank), dtype),
        v=spec((b, s, m.qk_rope_head_dim), dtype),
        pos=spec((b, s), jnp.int32),
    )


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_shapes(cfg) -> ShapeTree:
    d, hd, dt = cfg.d_model, cfg.head_size, pdtype(cfg)
    return {
        "wq": spec((d, cfg.n_heads * hd), dt),
        "wk": spec((d, cfg.n_kv_heads * hd), dt),
        "wv": spec((d, cfg.n_kv_heads * hd), dt),
        "wo": spec((cfg.n_heads * hd, d), dt),
    }


def cross_kv(p: Params, enc: jax.Array, cfg):
    b, s, _ = enc.shape
    hd = cfg.head_size
    k = (enc @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (enc @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    return k, v


def cross_apply(p: Params, x: jax.Array, kv: tuple[jax.Array, jax.Array], cfg,
                numerics) -> jax.Array:
    b, s, _ = x.shape
    hd = cfg.head_size
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k, v = kv
    sk = k.shape[1]
    qp = jnp.zeros((b, s), jnp.int32)
    kp = jnp.zeros((b, sk), jnp.int32)
    o = attention_core(q, k, v, qp, kp, numerics, causal=False)
    return o.reshape(b, s, -1) @ p["wo"]
