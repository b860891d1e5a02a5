"""Pallas TPU kernel: design-space envelope computation (paper §II-A).

The generation hot spot is, per region, the pair of per-sum-t envelopes over
divided differences of the integer bounds L, U:

    m(t) = min_{x<y, x+y=t} (U[y]+1-L[x])/(y-x)
    M(t) = max_{x<y, x+y=t} (L[y]-U[x]-1)/(y-x)

Splitting by the parity of t turns both into center-stencil reductions
(DESIGN.md §4):

    m_even[j] = min_{e>=1} (U[j+e]+1-L[j-e]) / (2e)        (t = 2j)
    m_odd[j]  = min_{e>=0} (U[j+1+e]+1-L[j-e]) / (2e+1)    (t = 2j+1)

which map onto the TPU as: L/U rows padded to 3N and resident in VMEM
(N <= 8192 -> ~200 KiB), grid over j-tiles of 128 lanes, fori_loop over the
offset e with always-in-bounds dynamic slices plus per-lane validity masks.
O(N^2) work with unit-stride vector loads and no scatters — the TPU-native
replacement for the paper's PyPy scalar loops.

These kernels run in interpret mode only: Mosaic rejects the (1, 3n) row
blocks (the (8, 128) tiling rule) and the per-offset lane
``dynamic_slice``s, so ``kernels/dspace/ops.py`` refuses to dispatch them
on a TPU (tests/kernels/test_tpu_compile.py keeps the rejection pinned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TILE = 128
BIG = 3.4e38  # python float: becomes an inline constant, not a captured array


def _parity_reduce(l_row, u_row, j0, n: int):
    """Shared kernel body: the per-offset parity-split center-stencil
    reduction over one padded (1, 3n) row at tile start ``j0``. Returns
    (m_even, m_odd, M_even, M_odd) tiles of shape (1, TILE)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
    j = j0 + lane  # global center indices, (1, TILE)

    def body(e, carry):
        me, mo, be, bo = carry
        # padded-row starts are always in bounds: start in [1, 3n - TILE]
        l_lo = jax.lax.dynamic_slice(l_row, (0, j0 - e + n), (1, TILE))
        u_lo = jax.lax.dynamic_slice(u_row, (0, j0 - e + n), (1, TILE))
        u_hi_e = jax.lax.dynamic_slice(u_row, (0, j0 + e + n), (1, TILE))
        l_hi_e = jax.lax.dynamic_slice(l_row, (0, j0 + e + n), (1, TILE))
        u_hi_o = jax.lax.dynamic_slice(u_row, (0, j0 + 1 + e + n), (1, TILE))
        l_hi_o = jax.lax.dynamic_slice(l_row, (0, j0 + 1 + e + n), (1, TILE))
        ok_lo = (j - e) >= 0
        ef = e.astype(jnp.float32)
        # even: pairs (j-e, j+e), e >= 1
        ok_e = ok_lo & ((j + e) <= (n - 1)) & (e >= 1)
        de_up = (u_hi_e + 1.0 - l_lo) / (2.0 * ef)
        de_lo = (l_hi_e - u_lo - 1.0) / (2.0 * ef)
        me = jnp.minimum(me, jnp.where(ok_e, de_up, BIG))
        be = jnp.maximum(be, jnp.where(ok_e, de_lo, -BIG))
        # odd: pairs (j-e, j+1+e), e >= 0
        ok_o = ok_lo & ((j + 1 + e) <= (n - 1))
        do_up = (u_hi_o + 1.0 - l_lo) / (2.0 * ef + 1.0)
        do_lo = (l_hi_o - u_lo - 1.0) / (2.0 * ef + 1.0)
        mo = jnp.minimum(mo, jnp.where(ok_o, do_up, BIG))
        bo = jnp.maximum(bo, jnp.where(ok_o, do_lo, -BIG))
        return me, mo, be, bo

    init = (jnp.full((1, TILE), BIG, jnp.float32), jnp.full((1, TILE), BIG, jnp.float32),
            jnp.full((1, TILE), -BIG, jnp.float32), jnp.full((1, TILE), -BIG, jnp.float32))
    return jax.lax.fori_loop(0, n, body, init)


def _envelope_kernel(l_ref, u_ref, me_ref, mo_ref, be_ref, bo_ref, *, n: int,
                     tile_axis: int = 0):
    """Inputs are rows padded to (1, 3n): real data in [n, 2n).

    me/mo: m(t) even/odd; be/bo: M(t) even/odd. ``tile_axis`` is the grid
    axis carrying the j-tile index (axis 1 when a leading region axis is
    present, as in ``envelopes_parity_batched``).
    """
    j0 = pl.program_id(tile_axis) * TILE
    me, mo, be, bo = _parity_reduce(l_ref[...], u_ref[...], j0, n)
    me_ref[...] = me
    mo_ref[...] = mo
    be_ref[...] = be
    bo_ref[...] = bo


def _envelope_kernel_fleet(l_ref, u_ref, me_ref, mo_ref, be_ref, bo_ref, *,
                           n: int):
    """Fleet variant: blocks carry a (probe, region) prefix — grid axes are
    (probe, region, j-tile) — and the row body is shared."""
    j0 = pl.program_id(2) * TILE
    me, mo, be, bo = _parity_reduce(l_ref[...].reshape(1, -1),
                                    u_ref[...].reshape(1, -1), j0, n)
    me_ref[...] = me.reshape(1, 1, TILE)
    mo_ref[...] = mo.reshape(1, 1, TILE)
    be_ref[...] = be.reshape(1, 1, TILE)
    bo_ref[...] = bo.reshape(1, 1, TILE)


def envelopes_parity(l_arr: jax.Array, u_arr: jax.Array,
                     interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Returns (m_even, m_odd, M_even, M_odd), each (N,) float32.

    Entries without any valid pair hold +/-3.4e38 sentinels.
    """
    n = l_arr.shape[-1]
    assert n % TILE == 0 and n >= TILE, n
    l2 = jnp.pad(l_arr.astype(jnp.float32), (n, n)).reshape(1, 3 * n)
    u2 = jnp.pad(u_arr.astype(jnp.float32), (n, n)).reshape(1, 3 * n)
    kernel = functools.partial(_envelope_kernel, n=n)
    out_spec = pl.BlockSpec((1, TILE), lambda i: (0, i))
    shape = jax.ShapeDtypeStruct((1, n), jnp.float32)
    me, mo, be, bo = pl.pallas_call(
        kernel,
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((1, 3 * n), lambda i: (0, 0))] * 2,
        out_specs=[out_spec] * 4,
        out_shape=[shape] * 4,
        interpret=interpret_mode(interpret),
    )(l2, u2)
    return me[0], mo[0], be[0], bo[0]


def envelopes_parity_fleet(l_arr: jax.Array, u_arr: jax.Array,
                           interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Fleet-stacked variant: ``(P, B, n)`` probe stacks in, four
    ``(P, B, n)`` parity envelopes out of ONE ``pallas_call`` with grid
    ``(probe, region, n // TILE)``.

    This is the §V scale move one level up from ``envelopes_parity_batched``:
    the whole manifest's probes become one device program whose probe axis
    the fleet engine shards across devices (kernels/dspace/ops.py).
    """
    p, b, n = l_arr.shape
    assert n % TILE == 0 and n >= TILE, n
    l2 = jnp.pad(l_arr.astype(jnp.float32), ((0, 0), (0, 0), (n, n)))
    u2 = jnp.pad(u_arr.astype(jnp.float32), ((0, 0), (0, 0), (n, n)))
    kernel = functools.partial(_envelope_kernel_fleet, n=n)
    out_spec = pl.BlockSpec((1, 1, TILE), lambda q, r, i: (q, r, i))
    shape = jax.ShapeDtypeStruct((p, b, n), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(p, b, n // TILE),
        in_specs=[pl.BlockSpec((1, 1, 3 * n), lambda q, r, i: (q, r, 0))] * 2,
        out_specs=[out_spec] * 4,
        out_shape=[shape] * 4,
        interpret=interpret_mode(interpret),
    )(l2, u2)


def envelopes_parity_batched(l_arr: jax.Array, u_arr: jax.Array,
                             interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Batched-region variant: ``(B, n)`` rows in, four ``(B, n)`` parity
    envelopes out of ONE ``pallas_call`` with grid ``(B, n // TILE)``.

    This is what lets the generator replace ``2^R`` per-region pool
    round-trips with a single device program (core/batched.py).
    """
    b, n = l_arr.shape
    assert n % TILE == 0 and n >= TILE, n
    l2 = jnp.pad(l_arr.astype(jnp.float32), ((0, 0), (n, n)))
    u2 = jnp.pad(u_arr.astype(jnp.float32), ((0, 0), (n, n)))
    kernel = functools.partial(_envelope_kernel, n=n, tile_axis=1)
    out_spec = pl.BlockSpec((1, TILE), lambda r, i: (r, i))
    shape = jax.ShapeDtypeStruct((b, n), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(b, n // TILE),
        in_specs=[pl.BlockSpec((1, 3 * n), lambda r, i: (r, 0))] * 2,
        out_specs=[out_spec] * 4,
        out_shape=[shape] * 4,
        interpret=interpret_mode(interpret),
    )(l2, u2)
