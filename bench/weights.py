"""Random weights from the seed, made on the device in one jitted program.

The same seed gives the same weights on every backend (threefry), so the
plain reference can make them again after the program's state is freed.
Leaves are drawn in sorted path order; a leaf stacked over layers is drawn
one layer at a time (``lax.map``), so no float32 copy of a whole stack is
ever alive. Norm gains are 1 + 0.05 N(0, 1), token embeddings N(0, 1),
every other matrix N(0, 1) / sqrt(fan-in).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key from a seed of any size (the upper 32 bits are folded in, not
    dropped) and a stream number."""
    seed %= 1 << 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def _names(shapes):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = ["/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]
    return names, [s for _, s in flat], treedef


def _draw(key, name: str, shape, dtype):
    if name.endswith("scale"):
        return (1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    std = 1.0 if name.endswith("tok") else 1.0 / math.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


_BUILDERS: dict = {}


def make(shapes, seed: int, stacked_prefix: str = "segments/"):
    """The weights of shape tree ``shapes`` for ``seed``: one jitted call.
    Leaves under ``stacked_prefix`` carry a leading layer axis."""
    names, specs, treedef = _names(shapes)
    sig = (tuple(names), tuple((s.shape, str(s.dtype)) for s in specs),
           stacked_prefix)
    if sig not in _BUILDERS:
        _BUILDERS[sig] = jax.jit(_builder(names, specs, treedef,
                                          stacked_prefix))
    return _BUILDERS[sig](seed_key(seed, 1))


def _builder(names, specs, treedef, stacked_prefix):

    def build(key):
        leaves = []
        for i, (name, s) in enumerate(zip(names, specs)):
            k = jax.random.fold_in(key, i)
            if name.startswith(stacked_prefix):
                ks = jax.random.split(k, s.shape[0])
                leaves.append(jax.lax.map(
                    lambda kk, name=name, s=s: _draw(kk, name, s.shape[1:],
                                                     s.dtype), ks))
            else:
                leaves.append(_draw(k, name, s.shape, s.dtype))
        return jax.tree.unflatten(treedef, leaves)

    return build
