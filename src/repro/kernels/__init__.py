"""Pallas TPU kernels for the paper's table datapath.

Every kernel entry point takes ``interpret: bool | None = None``; ``None``
resolves through :func:`interpret_mode` — compiled (Mosaic) on a TPU
backend, the Pallas interpreter everywhere else. Nothing defaults to
interpret mode on a TPU.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit bool wins, ``None``
    means "compile on a TPU, interpret off it" (the CPU has no Mosaic
    lowering)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
