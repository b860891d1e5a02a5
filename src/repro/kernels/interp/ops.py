"""Jitted public wrappers: evaluate one TableDesign — or a whole compiled
InterpLibrary — on arbitrary-shape codes."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.table import TableDesign
from repro.kernels.interp.kernel import (BLOCK_ROWS, LANES, interp_eval_2d,
                                         library_eval_2d, library_walk_2d)
from repro.kernels.interp.ref import (interp_eval_ref, interp_eval_wide,
                                      library_eval_ref, library_walk_ref)
from repro.launch.sharding import local_map, rule_spec


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def assert_rom_replicated(*operands: jax.Array) -> None:
    """SPMD contract of every kernel in this module: the ROM-side operands
    (coeffs / meta / walk / dp) must be **replicated** on a mesh. The fused
    kernels gather table rows by local index — a partitioned ROM would turn
    each gather into a cross-device lookup XLA resolves with collectives (or
    worse, wrong rows under ``shard_map``). Sharded serving therefore places
    the library with ``NamedSharding(mesh, P())`` per leaf and calls this
    once at placement time; it is a no-op for tracers, committed single-
    device arrays, and non-array leaves.
    """
    from jax.sharding import NamedSharding

    for x in operands:
        if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
            continue
        s = x.sharding
        if isinstance(s, NamedSharding) and any(
                p is not None for p in s.spec):
            raise ValueError(
                f"interp ROM operand {x.shape} is partitioned "
                f"({s.spec}); the fused kernels require a replicated ROM "
                f"— place the library with a fully-replicated sharding")


@partial(jax.jit, static_argnames=("eval_bits", "k", "sq_trunc", "lin_trunc",
                                   "degree", "interpret"))
def _eval_padded(codes, coeffs, *, eval_bits, k, sq_trunc, lin_trunc, degree,
                 interpret):
    n = codes.size
    tile = BLOCK_ROWS * LANES
    pad = (-n) % tile
    flat = jnp.pad(codes.reshape(-1), (0, pad)).reshape(-1, LANES)
    out = interp_eval_2d(flat, coeffs, eval_bits=eval_bits, k=k,
                         sq_trunc=sq_trunc, lin_trunc=lin_trunc,
                         degree=degree, interpret=interpret)
    return out.reshape(-1)[:n].reshape(codes.shape)


def table_eval(codes: jax.Array, design: TableDesign,
               use_kernel: bool = True, interpret: bool | None = None) -> jax.Array:
    """Evaluate ``design`` on int32 codes; Pallas kernel or jnp-ref path.

    Designs whose coefficients exceed int32 (wide-output reciprocals) take
    the emulated-int64 jnp path regardless of ``use_kernel`` — the int32
    ROM cannot hold them, and the historical fallback silently wrapped them
    through ``device_coeffs()`` (ROADMAP regression, DESIGN.md §7.5).
    """
    codes = codes.astype(jnp.int32)
    if not design.fits_int32:
        return interp_eval_wide(codes, design.device_coeffs_wide(),
                                eval_bits=design.eval_bits, k=design.k,
                                sq_trunc=design.sq_trunc,
                                lin_trunc=design.lin_trunc,
                                degree=design.degree)
    if not use_kernel:
        return interp_eval_ref(codes, design.device_coeffs(),
                               eval_bits=design.eval_bits,
                               k=design.k, sq_trunc=design.sq_trunc,
                               lin_trunc=design.lin_trunc, degree=design.degree)
    coeffs = design.device_coeffs(checked=True)
    return _eval_padded(codes, coeffs, eval_bits=design.eval_bits, k=design.k,
                        sq_trunc=design.sq_trunc, lin_trunc=design.lin_trunc,
                        degree=design.degree, interpret=interpret)


def _elementwise_spec(x: jax.Array) -> P:
    """Mesh layout for an elementwise table read: batch on the leading
    axis, the MLP axis on the last — the layout the activation sites
    already have, so ``local_map`` moves no data."""
    if x.ndim < 2:
        return rule_spec(("batch",) * x.ndim, x.shape)
    return rule_spec(("batch",) + (None,) * (x.ndim - 2) + ("mlp",), x.shape)


@partial(jax.jit, static_argnames=("interpret",))
def _library_eval_padded(codes, fids, coeffs, meta, *, interpret):
    n = codes.size
    tile = BLOCK_ROWS * LANES
    pad = (-n) % tile
    flat = jnp.pad(codes.reshape(-1), (0, pad)).reshape(-1, LANES)
    flat_f = jnp.pad(fids.reshape(-1), (0, pad)).reshape(-1, LANES)
    out = library_eval_2d(flat, flat_f, coeffs, meta, interpret=interpret)
    return out.reshape(-1)[:n].reshape(codes.shape)


def library_eval(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                 meta: jax.Array, use_kernel: bool = True,
                 interpret: bool | None = None) -> jax.Array:
    """Fused multi-function evaluation: element i reads function
    ``fids[i]``'s table row. One kernel program serves the entire library —
    every call site lowers the same (shapes, F, R_max) executable, instead
    of one Pallas specialization per table.

    codes/fids: int32, any (matching) shape; coeffs: (F, R_max, 3) int32
    padded ROM; meta: (F, 5) int32 datapath rows.
    """
    codes = codes.astype(jnp.int32)
    fids = jnp.broadcast_to(jnp.asarray(fids, jnp.int32), codes.shape)
    if not use_kernel:
        return library_eval_ref(codes, fids, coeffs, meta)
    spec = _elementwise_spec(codes)
    return local_map(
        lambda c, f, co, m: _library_eval_padded(c, f, co, m,
                                                 interpret=interpret),
        (codes, fids, coeffs, meta), (spec, spec, P(), P()), spec)


@partial(jax.jit, static_argnames=("interpret",))
def _library_walk_padded(codes, fids, coeffs, walk, dp, *, interpret):
    n = codes.size
    tile = BLOCK_ROWS * LANES
    pad = (-n) % tile
    flat = jnp.pad(codes.reshape(-1), (0, pad)).reshape(-1, LANES)
    flat_f = jnp.pad(fids.reshape(-1), (0, pad)).reshape(-1, LANES)
    out = library_walk_2d(flat, flat_f, coeffs, walk, dp, interpret=interpret)
    return out.reshape(-1)[:n].reshape(codes.shape)


def library_walk(codes: jax.Array, fids: jax.Array, coeffs: jax.Array,
                 walk: jax.Array, dp: jax.Array, use_kernel: bool = True,
                 interpret: bool | None = None) -> jax.Array:
    """Generalized fused evaluation over a mixed uniform/segmented library:
    element i walks function ``fids[i]``'s slot whatever its layout. This
    is ``library_eval`` minus its all-uniform restriction — the per-slot
    address decode (region index vs segment-index table) rides per-function
    ``walk`` rows and per-leaf ``dp`` datapath rows instead of one (F, 5)
    meta operand.

    codes/fids: int32, any (matching) shape; coeffs: (F, R_max, 3) int32
    padded ROM; walk: (F, 5) int32; dp: (L, 5) int32.
    """
    codes = codes.astype(jnp.int32)
    fids = jnp.broadcast_to(jnp.asarray(fids, jnp.int32), codes.shape)
    if not use_kernel:
        return library_walk_ref(codes, fids, coeffs, walk, dp)
    spec = _elementwise_spec(codes)
    return local_map(
        lambda c, f, co, w, d: _library_walk_padded(c, f, co, w, d,
                                                    interpret=interpret),
        (codes, fids, coeffs, walk, dp), (spec, spec, P(), P(), P()), spec)
