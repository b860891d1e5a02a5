"""Mamba2 (state-space duality) mixer: chunked SSD for train/prefill and a
single-step state update for decode.

All exponentials (the decay factors exp(dt*A) with dt >= 0, A < 0) and the
dt softplus run through the numerics backend, so the paper's tables certify
the SSM recurrence too (DESIGN.md §6). Chunked SSD follows arXiv:2405.21060
§6: quadratic attention-like compute inside chunks (matmul-friendly) plus a
linear recurrence over chunk states.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.launch.sharding import constrain
from repro.models.layers import Params, ShapeTree, pdtype, spec


class SSMState(NamedTuple):
    conv: jax.Array  # (B, d_conv-1, conv_dim) shift register
    ssm: jax.Array  # (B, H, P, N) recurrent state


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_dim


def ssm_shapes(cfg) -> ShapeTree:
    s, dt = cfg.ssm, pdtype(cfg)
    d_inner, n_heads, conv_dim = _dims(cfg)
    return {
        "in_proj": spec((cfg.d_model, 2 * d_inner + 2 * s.n_groups * s.d_state + n_heads), dt),
        "conv_w": spec((s.d_conv, conv_dim), dt),
        "conv_b": spec((conv_dim,), dt),
        "a_log": spec((n_heads,), jnp.float32),
        "dt_bias": spec((n_heads,), jnp.float32),
        "d_skip": spec((n_heads,), jnp.float32),
        "norm": {"scale": spec((d_inner,), dt)},
        "out_proj": spec((d_inner, cfg.d_model), dt),
    }


def _split_proj(p: Params, x: jax.Array, cfg):
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner : d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim :]
    return z, xbc, dt


def _conv_scan(p: Params, xbc: jax.Array, cfg, numerics) -> jax.Array:
    """Causal depthwise conv over sequence (train/prefill path)."""
    s = cfg.ssm
    pad = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * p["conv_w"][i]
        for i in range(s.d_conv)
    )
    return numerics.silu(out + p["conv_b"])


def _gated_norm(p: Params, y: jax.Array, z: jax.Array, numerics) -> jax.Array:
    g = y * numerics.silu(z)
    return numerics.rmsnorm(g, p["norm"]["scale"].astype(jnp.float32)).astype(y.dtype)


def ssd_chunked(x, dt, a, b_mat, c_mat, d_skip, cfg, numerics,
                h0: jax.Array | None = None):
    """Chunked SSD.

    x: (B,S,H,P); dt: (B,S,H); a: (H,) < 0; b_mat/c_mat: (B,S,G,N).
    Returns (y: (B,S,H,P), h_final: (B,H,P,N)).
    """
    s_cfg = cfg.ssm
    bsz, seq, h, p_dim = x.shape
    g = s_cfg.n_groups
    hg = h // g
    q = min(s_cfg.chunk, seq)
    assert seq % q == 0, (seq, q)
    nc = seq // q

    xr = x.reshape(bsz, nc, q, g, hg, p_dim)
    dtr = dt.reshape(bsz, nc, q, h)
    br = b_mat.reshape(bsz, nc, q, g, s_cfg.d_state)
    cr = c_mat.reshape(bsz, nc, q, g, s_cfg.d_state)
    dta = dtr * a  # (B,nc,Q,H) <= 0
    cum = jnp.cumsum(dta, axis=2)  # within-chunk cumulative decay

    # intra-chunk (quadratic in Q, matmul-friendly)
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cr, br, preferred_element_type=jnp.float32)
    seg = cum[..., :, None, :] - cum[..., None, :, :]  # (B,nc,Q,S,H): cum_i - cum_j <= 0 for i>=j
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(tri[None, None, :, :, None], numerics.exp_neg(jnp.minimum(seg, 0.0)), 0.0)
    dgr = decay.reshape(bsz, nc, q, q, g, hg)  # (B,nc,Q,S,G,HG)
    # mat[b,c,g,q,s,m] = (C_q.B_s) * exp(cum_q-cum_s) * dt_s
    mat = (cb[:, :, :, :, :, None] * dgr.transpose(0, 1, 4, 2, 3, 5)
           * dtr.reshape(bsz, nc, q, g, hg).transpose(0, 1, 3, 2, 4)[:, :, :, None, :, :])
    y_intra = jnp.einsum("bcgqsm,bcsgmp->bcqgmp", mat, xr,
                         preferred_element_type=jnp.float32)

    # chunk states: S_c = sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
    to_end = numerics.exp_neg(cum[:, :, -1:, :] - cum)  # arg <= 0
    wts = (to_end * dtr).reshape(bsz, nc, q, g, hg)
    states = jnp.einsum("bcqgm,bcqgn,bcqgmp->bcgmpn", wts, br, xr,
                        preferred_element_type=jnp.float32)

    # inter-chunk linear recurrence over chunk states
    chunk_decay = numerics.exp_neg(jnp.sum(dta, axis=2))  # exp(sum dta), arg <= 0
    cd = chunk_decay.reshape(bsz, nc, g, hg)

    def step(h_prev, xs):
        st, dec = xs  # (B,G,HG,P,N), (B,G,HG)
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev

    init = (jnp.zeros((bsz, g, hg, p_dim, s_cfg.d_state), jnp.float32)
            if h0 is None else h0.reshape(bsz, g, hg, p_dim, s_cfg.d_state))
    h_last, h_prevs = jax.lax.scan(step, init,
                                   (states.transpose(1, 0, 2, 3, 4, 5), cd.transpose(1, 0, 2, 3)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4, 5)  # (B,nc,G,HG,P,N)

    # inter-chunk contribution: C_i . (exp(cum_i) * h_prev)
    from_start = numerics.exp_neg(cum).reshape(bsz, nc, q, g, hg)  # exp(cum_i), cum <= 0
    y_inter = jnp.einsum("bcqgn,bcgmpn,bcqgm->bcqgmp", cr, h_prevs, from_start,
                         preferred_element_type=jnp.float32)

    y = (y_intra + y_inter).reshape(bsz, seq, h, p_dim)
    y = y + x * d_skip[None, None, :, None]
    return y.astype(x.dtype), h_last.reshape(bsz, h, p_dim, s_cfg.d_state)


def ssm_train(p: Params, x: jax.Array, cfg, numerics) -> jax.Array:
    y, _ = _ssm_forward(p, x, cfg, numerics)
    return y


def _ssm_forward(p: Params, x: jax.Array, cfg, numerics,
                 h0: jax.Array | None = None):
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    z, xbc, dt = _split_proj(p, x, cfg)
    xbc = _conv_scan(p, xbc, cfg, numerics)
    x_ssm = xbc[..., :d_inner]
    b_mat = xbc[..., d_inner : d_inner + s.n_groups * s.d_state]
    c_mat = xbc[..., d_inner + s.n_groups * s.d_state :]
    bsz, seq, _ = x.shape
    x_ssm = constrain(x_ssm.reshape(bsz, seq, n_heads, s.head_dim),
                      ("batch", None, "heads", None))
    b_mat = b_mat.reshape(bsz, seq, s.n_groups, s.d_state)
    c_mat = c_mat.reshape(bsz, seq, s.n_groups, s.d_state)
    dt_f = numerics.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y, h_last = ssd_chunked(x_ssm, dt_f, a, b_mat, c_mat, p["d_skip"], cfg, numerics, h0)
    y = _gated_norm(p, y.reshape(bsz, seq, d_inner), z, numerics)
    return y @ p["out_proj"], h_last


def ssm_prefill(p: Params, x: jax.Array, cfg, numerics):
    s = cfg.ssm
    d_inner, _, conv_dim = _dims(cfg)
    y, h_last = _ssm_forward(p, x, cfg, numerics)
    _, xbc, _ = _split_proj(p, x, cfg)
    tail = xbc[:, -(s.d_conv - 1):, :]
    pad = s.d_conv - 1 - tail.shape[1]
    if pad > 0:
        tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
    return y, SSMState(conv=tail, ssm=h_last)


def ssm_decode(p: Params, x: jax.Array, state: SSMState, cfg, numerics,
               layer: jax.Array | None = None):
    """x: (B, 1, d). ``layer``: ``state`` is the segment's layer stack;
    this layer reads its slice and writes its new state back at ``layer``
    in place (the whole state changes every token; it is small)."""
    if layer is not None:
        y, new = ssm_decode(p, x, jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False),
            state), cfg, numerics)
        return y, jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
            state, new)
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    bsz = x.shape[0]
    z, xbc, dt = _split_proj(p, x, cfg)  # (B,1,*)
    window = jnp.concatenate([state.conv, xbc], axis=1)  # (B, d_conv, conv_dim)
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc1 = numerics.silu(conv_out)[:, None, :]
    x_ssm = xbc1[..., :d_inner].reshape(bsz, n_heads, s.head_dim)
    b_mat = xbc1[..., d_inner : d_inner + s.n_groups * s.d_state].reshape(bsz, s.n_groups, s.d_state)
    c_mat = xbc1[..., d_inner + s.n_groups * s.d_state :].reshape(bsz, s.n_groups, s.d_state)
    dt_f = numerics.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["a_log"])
    decay = numerics.exp_neg(dt_f * a)  # exp(dt*A), arg <= 0 since A < 0
    hg = n_heads // s.n_groups
    xg = x_ssm.reshape(bsz, s.n_groups, hg, s.head_dim)
    dtg = dt_f.reshape(bsz, s.n_groups, hg)
    upd = jnp.einsum("bgm,bgn,bgmp->bgmpn", dtg, b_mat, xg,
                     preferred_element_type=jnp.float32)
    h = state.ssm.reshape(bsz, s.n_groups, hg, s.head_dim, s.d_state)
    h_new = h * decay.reshape(bsz, s.n_groups, hg)[..., None, None] + upd
    y = jnp.einsum("bgn,bgmpn->bgmp", c_mat, h_new,
                   preferred_element_type=jnp.float32)
    y = y.reshape(bsz, n_heads, s.head_dim) + x_ssm * p["d_skip"][None, :, None]
    y = _gated_norm(p, y.reshape(bsz, 1, d_inner).astype(x.dtype), z, numerics)
    new_state = SSMState(conv=window[:, 1:, :], ssm=h_new.reshape(bsz, n_heads, s.head_dim, s.d_state))
    return y @ p["out_proj"], new_state


def ssm_state_specs(cfg, b: int, dtype) -> SSMState:
    s = cfg.ssm
    d_inner, n_heads, conv_dim = _dims(cfg)
    return SSMState(
        conv=spec((b, s.d_conv - 1, conv_dim), dtype),
        ssm=spec((b, n_heads, s.head_dim, s.d_state), jnp.float32),
    )
