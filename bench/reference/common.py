"""Pieces shared by the plain references: float32 math, one layer at a time.

A reference runs one sequence (the prompt followed by the served tokens) and
returns the logits at the rows where the served tokens were produced. Every
weight is upcast from its served bfloat16 to float32 (exact), every product
runs under ``jax.default_matmul_precision("highest")``, and the layers run one
jitted call each, so the float32 copy of only one layer is alive at a time.

``quant="fp8"`` is the control for a bfloat16 configuration: the same
forward with every matrix product taken in float8 (e4m3), weights scaled per
output column and activations per row, accumulated in float32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
PAD = 512  # sequences are padded to a multiple of this (causal: no effect)


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def _lower(x, quant, axis):
    return _fp8(x, axis) if quant == "fp8" else x


def mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, or with the operands rounded to
    fp8, the control's precision (``quant="fp8"``)."""
    x, w = _lower(x, quant, -1), _lower(w, quant, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, quant, a_axis=-1, b_axis=-1):
    a, b = _lower(a, quant, a_axis), _lower(b, quant, b_axis)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gamma.astype(F32)


def rope(x, positions, theta):
    """Rotary embedding on the last axis of x (S, H, D), rotating its two
    halves against each other; positions (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv  # (S, D/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_attention(q, k, v, scale, quant):
    """q (S, H, Dk), k (S, H, Dk), v (S, H, Dv) -> (S, H, Dv)."""
    s = einsum("qhd,khd->hqk", q, k, quant) * scale
    n = q.shape[0]
    mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return einsum("hqk,khd->qhd", p, v, quant, a_axis=-1, b_axis=0)


def swiglu(x, wi, wo, quant):
    h = mm(x, wi.astype(F32), quant)
    f = h.shape[-1] // 2
    return mm(jax.nn.silu(h[..., :f]) * h[..., f:], wo.astype(F32), quant)


def layer_slice(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


@partial(jax.jit, static_argnames=("eps", "quant"))
def head_rows(h, gamma, head, rows, *, eps, quant):
    """Final norm and logits of rows ``rows`` of h (S, d)."""
    x = rmsnorm(h[rows], gamma, eps)
    return mm(x, head.astype(F32), quant)


def forward_rows(params, tokens, rows, layer_fn, n_layers, eps, quant=None):
    """Logits (len(rows), V) of one sequence, layer by layer.

    ``layer_fn(h, layer_params, positions) -> h`` is the architecture's
    jitted block; the sequence is padded to a multiple of ``PAD`` so a few
    programs serve every length."""
    n = len(tokens)
    pad = (-n) % PAD
    toks = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    pos = jnp.arange(n + pad, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tok"][toks].astype(F32)
        stacked = params["segments"]["seg0"]["0"]
        for i in range(n_layers):
            h = layer_fn(h, layer_slice(stacked, i), pos, quant)
        return head_rows(h, params["final_norm"]["scale"],
                         params["embed"]["head"], jnp.asarray(rows, jnp.int32),
                         eps=eps, quant=quant)


def stacked_shapes(tree, n_layers):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_layers, *s.shape), s.dtype), tree)


def head_dim_scale(d: int) -> float:
    return 1.0 / math.sqrt(d)
