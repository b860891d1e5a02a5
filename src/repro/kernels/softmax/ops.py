"""Jitted wrappers for the fused approx-softmax kernels (per-table design
operands, or one library ROM operand for the whole datapath)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.table import TableDesign
from repro.launch.sharding import local_map, rule_spec
from repro.kernels.softmax.kernel import (BLOCK_ROWS, fused_softmax,
                                          fused_softmax_lib)
from repro.kernels.softmax.ref import fused_softmax_lib_ref, fused_softmax_ref
from repro.api import get_table


def _meta(design: TableDesign) -> dict:
    return {
        "in_bits": design.in_bits,
        "out_bits": design.out_bits,
        "eval": {
            "eval_bits": design.eval_bits,
            "k": design.k,
            "sq_trunc": design.sq_trunc,
            "lin_trunc": design.lin_trunc,
            "degree": design.degree,
        },
    }


def lib_meta(library, kind: str) -> dict:
    """The kernel meta dict of one library slot: the per-table ``_meta``
    fields plus the function's static ROM row offset (``fid``).

    A non-uniform (ROM v2) slot additionally carries its static
    ``seg_spec()`` tuple under ``eval["seg"]`` — the in-kernel ``_lut_rom``
    read and the jnp oracles route through the segment-index datapath when
    the key is present, so every fused consumer (softmax / rmsnorm /
    flashattn) decodes segmented slots with zero extra dispatches. Uniform
    slots omit the key entirely, keeping their meta dicts unchanged.
    """
    m = library.meta(kind)
    ev = {
        "eval_bits": m.eval_bits,
        "k": m.k,
        "sq_trunc": m.sq_trunc,
        "lin_trunc": m.lin_trunc,
        "degree": m.degree,
    }
    if m.segmented:
        ev["seg"] = m.seg_spec()
    return {
        "in_bits": m.in_bits,
        "out_bits": m.out_bits,
        "fid": library.func_id(kind),
        "eval": ev,
    }


def _pad_rows_lanes(xf: jax.Array, fill: float = 0.0):
    """Pad a (rows, d) block to the kernels' (8, 128) grid: rows to
    BLOCK_ROWS with ``fill`` (a finite value keeps the pad rows' math
    finite), features to a 128-lane multiple with zeros (masked by the
    kernels' ``d_valid``). Returns the padded block and ``d_valid`` (None
    when no lane padding was needed)."""
    rows, d = xf.shape
    pad_r, pad_d = (-rows) % BLOCK_ROWS, (-d) % 128
    if pad_r:
        xf = jnp.pad(xf, ((0, pad_r), (0, 0)), constant_values=fill)
    if pad_d:
        xf = jnp.pad(xf, ((0, 0), (0, pad_d)))
    return xf, (d if pad_d else None)


def approx_softmax_library(x: jax.Array, library, use_kernel: bool | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Library-bound fused softmax over the last axis.

    One ROM operand (the compiled :class:`repro.api.InterpLibrary` pytree
    leaf) feeds both in-kernel table reads — exp at its static func id,
    recip at its own — so a softmax is ONE kernel launch instead of a
    gather→eval→elementwise chain per transcendental. ``use_kernel=None``
    picks the Pallas kernel on TPU — any feature width: features off the
    128-lane grid are padded and masked in-kernel — and the bit-identical
    jnp ROM-gather oracle elsewhere. On a mesh the kernel runs per device
    on its rows (``local_map``)."""
    em, rm = lib_meta(library, "exp2neg"), lib_meta(library, "recip")
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return fused_softmax_lib_ref(xf, library.coeffs, em, rm).reshape(shape)

    def kernel(x, coeffs):  # rows are independent: any row sharding works
        xf = x.reshape(-1, d)
        xp, d_valid = _pad_rows_lanes(xf)
        out = fused_softmax_lib(xp, coeffs.reshape(-1, 3), em, rm,
                                r_max=coeffs.shape[1], d_valid=d_valid,
                                interpret=interpret)
        return out[:xf.shape[0], :d].reshape(x.shape)

    spec = rule_spec(("batch",) + (None,) * (x.ndim - 1), shape)
    return local_map(kernel, (x, library.coeffs), (spec, P()), spec)


def approx_softmax_fused(x: jax.Array,
                         exp_design: TableDesign | None = None,
                         recip_design: TableDesign | None = None,
                         use_kernel: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    """Fused softmax over the last axis; leading axes are flattened to rows.

    Rows are padded to the 8-row block; the feature dim must be a multiple
    of 128 (the serving attention shapes used by the examples all are).
    """
    exp_design = exp_design or get_table("exp2neg")
    recip_design = recip_design or get_table("recip")
    ec = exp_design.device_coeffs(checked=True)
    rc = recip_design.device_coeffs(checked=True)
    em, rm = _meta(exp_design), _meta(recip_design)
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    if not use_kernel:
        return fused_softmax_ref(xf, ec, rc, em, rm).reshape(shape)
    pad = (-rows) % BLOCK_ROWS
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = fused_softmax(xf, ec, rc, em, rm, interpret=interpret)
    return out[:rows].reshape(shape)
