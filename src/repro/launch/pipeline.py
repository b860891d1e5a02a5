"""GPipe-style pipeline parallelism over ``shard_map`` + ``lax.ppermute``.

The production dry-run mesh uses DP x TP (the right choice at these sizes on
a v5e-class pod); PP is provided for 1000+-node scaling headroom, where a
third mesh axis keeps TP domains inside an ICI-connected slice and pipelines
across slices.

Schedule: classic GPipe. ``n_stages`` devices each own ``layers/n_stages``
layers; ``n_micro`` microbatches stream through. Each outer tick every stage
(in parallel, SPMD) applies its block to its current microbatch and
``ppermute``s activations to the next stage. Bubble fraction is
``(S-1)/(M+S-1)``. The stage body is any ``(params, x) -> x`` function, so
models plug in per-segment.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_params, x_micro, stage_fn: Callable, mesh: Mesh,
                   axis: str = "stage"):
    """Run microbatches through pipeline stages.

    stage_params: pytree with leading dim = n_stages (stage-sharded).
    x_micro: (n_micro, mb, ...) microbatched input, replicated.
    stage_fn: (params_for_stage, x) -> y, applied by every stage.
    Returns (n_micro, mb, ...) outputs after all stages.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_spec, P()), out_specs=P(),
             check_vma=False)
    def run(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # this stage's slice
        idx = jax.lax.axis_index(axis)
        total = n_micro + n_stages - 1  # GPipe ticks incl. bubble
        buf = jnp.zeros_like(xs[0])  # current activation on this stage
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any left)
            feed = xs[jnp.clip(t, 0, n_micro - 1)]
            buf = jnp.where(idx == 0, jnp.where(t < n_micro, feed, buf), buf)
            # every stage processes its current microbatch
            y = stage_fn(params, buf)
            # last stage commits microbatch (t - (S-1)) once it's real
            out_slot = t - (n_stages - 1)
            commit = (idx == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_slot, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # rotate activations downstream
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, total, tick, (buf, outs))
        # outs live on the last stage; share them (replicated out_specs)
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
