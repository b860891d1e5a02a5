"""Shared model layers: norms, RoPE, MLPs, embeddings, parameter utilities.

Parameters are plain nested dicts of jnp arrays. Every layer exposes
``*_shapes(cfg) -> dict[name, jax.ShapeDtypeStruct]`` so the dry-run can
build abstract parameter trees without allocating, and ``init_tree`` turns
the same specs into real arrays for the smoke tests / examples.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Params = dict
ShapeTree = dict

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def pdtype(cfg) -> jnp.dtype:
    return _DTYPES[cfg.param_dtype]


def spec(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def stack_specs(tree: ShapeTree, n: int) -> ShapeTree:
    """Prepend a layer dimension to every leaf (scanned layer stacks)."""
    return jax.tree.map(lambda s: spec((n, *s.shape), s.dtype), tree)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key: jax.Array, shape: tuple, dtype, std: float) -> jax.Array:
    """One leaf's truncated-normal draw, jitted so the float32 sample fuses
    into the cast: a full-width bf16 leaf never materializes in float32."""
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def init_tree(key: jax.Array, shapes: ShapeTree, scale_rules: Callable[[str, Any], float] | None = None) -> Params:
    """Materialize a shape tree: truncated-normal fan-in init, zeros for
    biases/norm offsets, ones for norm scales."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))

    def one(path, s, k):
        name = "/".join(str(p.key) if hasattr(p, "key") else str(p) for p in path)
        if name.endswith(("bias", "b", "a_log", "dt_bias", "d_skip")):
            if name.endswith("a_log"):
                row = jnp.log(jnp.arange(1, s.shape[-1] + 1, dtype=jnp.float32))
                return jnp.broadcast_to(row, s.shape).astype(s.dtype)
            if name.endswith("d_skip"):
                return jnp.ones(s.shape, s.dtype)
            return jnp.zeros(s.shape, s.dtype)
        if name.endswith(("scale", "gamma")):
            return jnp.ones(s.shape, s.dtype)
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        if scale_rules is not None:
            std *= scale_rules(name, s)
        return _draw(k, s.shape, jnp.dtype(s.dtype), std)

    leaves = [one(p, s, k) for (p, s), k in zip(flat, keys)]
    return jax.tree.unflatten(treedef, leaves)


def count_params(shapes: ShapeTree) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


# ----------------------------------------------------------------- norms

def norm_shapes(cfg, d=None) -> ShapeTree:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": spec((d,), pdtype(cfg)), "bias": spec((d,), pdtype(cfg))}
    return {"scale": spec((d,), pdtype(cfg))}


def apply_norm(p: Params, x: jax.Array, cfg, numerics) -> jax.Array:
    if cfg.norm == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)
    return numerics.rmsnorm(x, p["scale"].astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------ RoPE

def rope_angles(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions: (...,) int32 -> cos/sin of shape (..., dim//2), fp32."""
    freqs = jnp.exp(-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim * math.log(theta))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D); cos/sin: (..., S, D/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# ------------------------------------------------------------------- MLP

def mlp_shapes(cfg, d_ff=None) -> ShapeTree:
    d, dt = cfg.d_model, pdtype(cfg)
    f = d_ff or cfg.d_ff
    if cfg.act == "silu":  # SwiGLU: gate + up + down
        return {"wi": spec((d, 2 * f), dt), "wo": spec((f, d), dt)}
    return {"wi": spec((d, f), dt), "wo": spec((f, d), dt)}


def apply_mlp(p: Params, x: jax.Array, cfg, numerics) -> jax.Array:
    h = x @ p["wi"]
    if cfg.act == "silu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = numerics.silu(gate) * up
    elif cfg.act == "gelu":
        h = numerics.gelu(h)
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(cfg.act)
    from repro.launch.sharding import constrain  # C3: reduce-scatter output
    return constrain(h @ p["wo"], ("batch", "seq", None))


# ------------------------------------------------------------- embeddings

def embed_shapes(cfg) -> ShapeTree:
    dt = pdtype(cfg)
    out: ShapeTree = {"tok": spec((cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        out["head"] = spec((cfg.d_model, cfg.vocab_size), dt)
    return out


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return p["tok"][tokens]


def lm_logits(p: Params, h: jax.Array) -> jax.Array:
    w = p["head"] if "head" in p else p["tok"].T
    return h @ w
