"""Jitted wrappers for the fused approx-RMSNorm kernels (per-table design
operand, or the whole-library ROM operand)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.table import TableDesign
from repro.kernels.rmsnorm.kernel import (BLOCK_ROWS, fused_rmsnorm,
                                          fused_rmsnorm_lib)
from repro.kernels.rmsnorm.ref import fused_rmsnorm_lib_ref, fused_rmsnorm_ref
from repro.launch.sharding import local_map, rule_spec
from repro.kernels.softmax.ops import _meta, _pad_rows_lanes, lib_meta
from repro.api import get_table


def approx_rmsnorm_library(x: jax.Array, gamma: jax.Array, library,
                           eps: float = 1e-6, use_kernel: bool | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Library-bound fused RMSNorm: the rsqrt table is read in-kernel from
    the compiled library's ROM operand (static func id). ``use_kernel=None``
    picks the Pallas kernel on TPU — any feature width: features off the
    128-lane grid are zero-padded and the mean keeps the real count — and
    the bit-identical jnp ROM-gather oracle elsewhere. On a mesh the kernel
    runs per device on its rows (``local_map``)."""
    meta = lib_meta(library, "rsqrt")
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return fused_rmsnorm_lib_ref(xf, gamma, library.coeffs, meta,
                                     eps).reshape(shape)

    def kernel(x, gamma, coeffs):  # rows are independent
        xf = x.reshape(-1, d)
        xp, d_valid = _pad_rows_lanes(xf, fill=1.0)
        g = gamma if d_valid is None else jnp.pad(gamma, (0, xp.shape[1] - d))
        out = fused_rmsnorm_lib(xp, g, coeffs.reshape(-1, 3), meta,
                                r_max=coeffs.shape[1], eps=eps,
                                d_valid=d_valid, interpret=interpret)
        return out[:xf.shape[0], :d].reshape(x.shape)

    spec = rule_spec(("batch",) + (None,) * (x.ndim - 1), shape)
    return local_map(kernel, (x, gamma, library.coeffs), (spec, P(), P()),
                     spec)


def approx_rmsnorm_fused(x: jax.Array, gamma: jax.Array,
                         design: TableDesign | None = None, eps: float = 1e-6,
                         use_kernel: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    design = design or get_table("rsqrt")
    coeffs = design.device_coeffs(checked=True)
    meta = _meta(design)
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    xf = x.reshape(rows, d)
    if not use_kernel:
        return fused_rmsnorm_ref(xf, gamma, coeffs, meta, eps).reshape(shape)
    pad = (-rows) % BLOCK_ROWS
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)), constant_values=1.0)
    out = fused_rmsnorm(xf, gamma, coeffs, meta, eps=eps, interpret=interpret)
    return out[:rows].reshape(shape)
