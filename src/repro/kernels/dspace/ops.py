"""Public wrapper: Pallas-accelerated envelope computation for generation.

``envelopes_pallas`` returns M(t), m(t) in the exact layout the core numpy
path (`repro.core.designspace.envelopes`) produces, so the generator can swap
implementations freely (``impl="pallas"`` in benchmarks).

``region_envelopes_device`` is the batched-engine entry point: one
``pallas_call`` over a grid of regions plus an on-device parity merge,
Eqn 9 feasibility, and the Eqn 7-8 a-interval divided-difference reduction —
the whole §II front half for all ``2^R`` regions in a single program.

The envelope kernels do not compile for a TPU yet (see
``kernels/dspace/kernel.py``): every entry point here runs them in
interpret mode and raises on a TPU backend rather than fall back to the
interpreter there. The default ``batched`` engine is host numpy and is
unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.dspace.kernel import (BIG, TILE, envelopes_parity,
                                         envelopes_parity_batched,
                                         envelopes_parity_fleet)
from repro.kernels.dspace.ref import envelopes_parity_ref

def _interpret_only(interpret: bool | None) -> bool:
    """The dspace kernels' mode: interpret, never on a TPU backend."""
    if not interpret_mode(interpret):
        raise NotImplementedError(
            "the dspace envelope kernels do not compile for a TPU (Mosaic "
            "rejects their (1, 3n) row blocks and lane dynamic_slices); use "
            "engine='batched' (host numpy) for exploration on a TPU host")
    return True


_PAD_L = -(2.0 ** 30)  # pad-lane sentinels: see envelopes_pallas docstring
_PAD_U = 2.0 ** 30


def _interleave(me, mo, be, bo, n: int):
    """Parity arrays -> (M, m) indexed by t in [0, 2n-2); index 0 is padding."""
    m = np.empty(2 * n - 2, dtype=np.float64)
    big_m = np.empty(2 * n - 2, dtype=np.float64)
    m[0::2] = np.asarray(me)[: n - 1]
    m[1::2] = np.asarray(mo)[: n - 1]
    big_m[0::2] = np.asarray(be)[: n - 1]
    big_m[1::2] = np.asarray(bo)[: n - 1]
    m[0], big_m[0] = np.inf, -np.inf
    m[m >= 3.0e38] = np.inf
    big_m[big_m <= -3.0e38] = -np.inf
    return big_m, m


def envelopes_pallas(L: np.ndarray, U: np.ndarray,
                     interpret: bool | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Drop-in replacement for core.designspace.envelopes via the kernel.

    Pads N up to a TILE multiple; pad lanes only ever appear as the *right*
    (y) operand of a kept-lane pair, so L[pad] = -2^30 / U[pad] = +2^30 make
    every pad-touching divided difference lose its min/max reduction.
    """
    interpret = _interpret_only(interpret)
    n = len(L)
    if n < 2:
        return np.full(1, -np.inf), np.full(1, np.inf)
    n_pad = max(((n + TILE - 1) // TILE) * TILE, TILE)
    lp = np.zeros(n_pad, np.float64)
    up = np.zeros(n_pad, np.float64)
    lp[:n], up[:n] = L, U
    if n_pad > n:
        lp[n:] = -(2.0**30)  # d_lo = (L[y]-U[x]-1)/.. -> -huge, loses max
        up[n:] = 2.0**30  # d_up = (U[y]+1-L[x])/.. -> +huge, loses min
    me, mo, be, bo = envelopes_parity(jnp.asarray(lp), jnp.asarray(up), interpret)
    big_m, m = _interleave(me, mo, be, bo, n_pad)
    return big_m[: 2 * n - 2], m[: 2 * n - 2]


def envelopes_ref_jnp(L: np.ndarray, U: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    n = len(L)
    if n < 2:
        return np.full(1, -np.inf), np.full(1, np.inf)
    me, mo, be, bo = envelopes_parity_ref(jnp.asarray(L), jnp.asarray(U))
    return _interleave(me, mo, be, bo, n)


# ---------------------------------------------------------------------------
# Batched engine: all regions in one device program
# ---------------------------------------------------------------------------

def _dd_max_rows(g: jax.Array, h: jax.Array) -> jax.Array:
    """Row-wise max_{x<y} (g[y]-h[x])/(y-x) on device, O(T^2) masked sweep.

    Right-pads ``g`` with ``-BIG`` so out-of-range y operands lose every max
    reduction (the padded slope keeps magnitude >= BIG / T, far below/above
    any real envelope slope)."""
    bsz, t = g.shape
    gp = jnp.pad(g, ((0, 0), (0, t)), constant_values=-BIG)

    def body(delta, best):
        gy = jax.lax.dynamic_slice(gp, (0, delta), (bsz, t))
        d = (gy - h) / delta.astype(jnp.float32)
        return jnp.maximum(best, jnp.max(d, axis=1))

    return jax.lax.fori_loop(1, t, body, jnp.full(bsz, -BIG, jnp.float32))


def _merge_reduce(me, mo, be, bo, n_real: int):
    """On-device parity merge (t = 2j -> even slot, t = 2j+1 -> odd slot),
    Eqn 9 feasibility, and the Eqn 7-8 a-interval reduction over stacked
    parity rows ``(rows, n_pad)``."""
    b, n_pad = me.shape
    m = jnp.stack([me[:, : n_pad - 1], mo[:, : n_pad - 1]], axis=2)
    big = jnp.stack([be[:, : n_pad - 1], bo[:, : n_pad - 1]], axis=2)
    m = m.reshape(b, 2 * n_pad - 2)[:, : 2 * n_real - 2]
    big = big.reshape(b, 2 * n_pad - 2)[:, : 2 * n_real - 2]
    mt, st = big[:, 1:], m[:, 1:]  # valid t range
    feas9 = jnp.all(mt < st, axis=1)
    a_lo = _dd_max_rows(mt, st)
    a_hi = -_dd_max_rows(-st, -mt)
    return big, m, a_lo, a_hi, feas9


@functools.partial(jax.jit, static_argnames=("n_real", "interpret"))
def _region_spaces_jit(l2: jax.Array, u2: jax.Array, n_real: int,
                       interpret: bool):
    """One pallas_call (grid over regions) + on-device parity merge,
    Eqn 9 feasibility, and the Eqn 7-8 a-interval reduction."""
    me, mo, be, bo = envelopes_parity_batched(l2, u2, interpret)
    return _merge_reduce(me, mo, be, bo, n_real)


def region_envelopes_device(L: np.ndarray, U: np.ndarray,
                            interpret: bool | None = None
                            ) -> tuple[np.ndarray, ...]:
    """§II front half for ALL regions: (M, m, a_lo, a_hi, feas9) arrays.

    Interpret mode only (raises on a TPU backend). M/m come back
    float64 in the core layout (index 0 placeholder, sentinels -> inf);
    envelope arithmetic itself runs in float32 — see DESIGN.md §9.
    """
    L = np.asarray(L)
    U = np.asarray(U)
    b, n = L.shape
    assert n >= 3, "trivial region widths are handled by the numpy engine"
    interpret = _interpret_only(interpret)
    n_pad = max(-(-n // TILE) * TILE, TILE)
    lp = np.full((b, n_pad), _PAD_L)
    up = np.full((b, n_pad), _PAD_U)
    lp[:, :n] = L
    up[:, :n] = U
    big, m, a_lo, a_hi, feas9 = _region_spaces_jit(
        jnp.asarray(lp, jnp.float32), jnp.asarray(up, jnp.float32),
        n_real=n, interpret=bool(interpret))
    big = np.asarray(big, np.float64)
    m = np.asarray(m, np.float64)
    m[m >= 3.0e38] = np.inf
    big[big <= -3.0e38] = -np.inf
    m[:, 0] = np.inf
    big[:, 0] = -np.inf
    return (big, m, np.asarray(a_lo, np.float64), np.asarray(a_hi, np.float64),
            np.asarray(feas9))


# ---------------------------------------------------------------------------
# Fleet engine: stacked (probe, region) grid, probe axis sharded over devices
# ---------------------------------------------------------------------------

def _fleet_impl(l3: jax.Array, u3: jax.Array, *, n_real: int,
                interpret: bool):
    """Per-shard fleet body: one pallas_call over the (probe, region, tile)
    grid plus the parity merge / feasibility / a-interval reduction on the
    flattened (probe*region) rows. Runs unchanged under shard_map — every
    row is independent, so sharding the probe axis is embarrassing."""
    p, b, n_pad = l3.shape
    me, mo, be, bo = envelopes_parity_fleet(l3, u3, interpret)

    def flat(a):
        return a.reshape(p * b, n_pad)

    big, m, a_lo, a_hi, feas9 = _merge_reduce(flat(me), flat(mo), flat(be),
                                              flat(bo), n_real)
    t = big.shape[1]
    return (big.reshape(p, b, t), m.reshape(p, b, t),
            a_lo.reshape(p, b), a_hi.reshape(p, b), feas9.reshape(p, b))


@functools.lru_cache(maxsize=32)
def _fleet_fn(shards: int, n_real: int, interpret: bool):
    """Compiled fleet front half for a device count (1 = single program;
    > 1 = ``jax.shard_map`` over the probe axis)."""
    impl = functools.partial(_fleet_impl, n_real=n_real, interpret=interpret)
    if shards <= 1:
        return jax.jit(impl)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:shards]), ("probe",))
    spec = P("probe")
    # check_vma=False: the replication checker cannot see through
    # pallas_call; every output is honestly probe-sharded anyway
    return jax.jit(jax.shard_map(impl, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=(spec,) * 5, check_vma=False))


def fleet_region_envelopes_device(L3, U3, shards: int | None = None,
                                  interpret: bool | None = None
                                  ) -> tuple[np.ndarray, ...]:
    """§II front half for a stacked probe fleet ``(P, B, N)``: one device
    program with a grid over (probe, region), the probe axis sharded over
    ``shards`` devices (``None``/1 = single program; capped at the local
    device count).

    Returns ``(M, m, a_lo, a_hi, feas9)`` flattened to probe-major rows
    ``(P*B, ...)`` in the core float64 layout. Float32 envelope arithmetic —
    the DESIGN.md §4/§9 contract (a marginal verdict can cost a retry, never
    an unsound artifact). Fleet ``±inf`` column sentinels are clamped to the
    kernel's finite pad values, which lose every reduction the same way.
    """
    L3 = np.asarray(L3)
    U3 = np.asarray(U3)
    p, b, n = L3.shape
    assert n >= 3, "trivial region widths are handled by the numpy engine"
    interpret = _interpret_only(interpret)
    shards = 1 if shards is None else max(1, min(int(shards),
                                                 len(jax.devices())))
    n_pad = max(-(-n // TILE) * TILE, TILE)
    p_pad = -(-p // shards) * shards  # sentinel probes pad the shard axis
    lp = np.full((p_pad, b, n_pad), _PAD_L)
    up = np.full((p_pad, b, n_pad), _PAD_U)
    lp[:p, :, :n] = np.where(np.isfinite(L3), L3, _PAD_L)
    up[:p, :, :n] = np.where(np.isfinite(U3), U3, _PAD_U)
    # n (the real width), NOT n_pad: the merge slices the TILE-pad t-slots
    # off before the a-interval reduction — their ~±2^30/(2e) sentinel
    # envelopes would otherwise win the dd max against steep real tables
    fn = _fleet_fn(shards, n, bool(interpret))
    big, m, a_lo, a_hi, feas9 = fn(jnp.asarray(lp, jnp.float32),
                                   jnp.asarray(up, jnp.float32))
    t = big.shape[-1]
    big = np.asarray(big, np.float64)[:p].reshape(p * b, t)
    m = np.asarray(m, np.float64)[:p].reshape(p * b, t)
    m[m >= 3.0e38] = np.inf
    big[big <= -3.0e38] = -np.inf
    m[:, 0] = np.inf
    big[:, 0] = -np.inf
    return (big, m,
            np.asarray(a_lo, np.float64)[:p].reshape(p * b),
            np.asarray(a_hi, np.float64)[:p].reshape(p * b),
            np.asarray(feas9)[:p].reshape(p * b))
