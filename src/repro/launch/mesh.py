"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
forces 512 host devices while tests/benches must see 1.

Axes:
  * ``pod``   — outer data parallelism across pods; crosses DCN. Gradient
    all-reduce on this axis is the slow hop (int8 EF compression applies).
  * ``data``  — data parallelism / FSDP (ZeRO-3 parameter+optimizer sharding)
    inside a pod; ICI.
  * ``model`` — tensor parallelism (Megatron column/row), expert parallelism
    for MoE, and sequence parallelism for long-context serving; ICI.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis in Auto mode: the sharding rules
    (``launch.sharding``) place work through GSPMD constraints, which jax
    accepts only on Auto axes (its default is Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def make_serve_mesh(data: int = 1, tp: int = 1, *, devices=None):
    """Serving mesh (DESIGN.md §17): ``("data", "tp")``.

    * ``data`` — batch-slot parallelism: the engine's KV pool is sharded on
      its slot axis, each device group decodes its own slice of the batch.
    * ``tp``   — tensor parallelism: attention / KV heads and the Megatron
      column/row weight shards (``sharding.SERVE_PARAM_RULES``).

    The axis names are distinct from the training meshes so serve processes
    size each axis independently of the trainer rules; ``sharding``'s rule
    tables carry ``("tp",)`` candidates for exactly this mesh. Extra local
    devices beyond ``data * tp`` are left unused (a forced-host-device CI
    run can carve a 2x2 mesh out of 8 fake devices).
    """
    devs = list(devices if devices is not None else jax.devices())
    need = int(data) * int(tp)
    if need < 1:
        raise ValueError(f"mesh axes must be positive, got {data}x{tp}")
    if len(devs) < need:
        raise ValueError(
            f"serve mesh {data}x{tp} needs {need} devices, "
            f"have {len(devs)}")
    import numpy as np

    from jax.sharding import Mesh
    arr = np.asarray(devs[:need], dtype=object).reshape(int(data), int(tp))
    return Mesh(arr, ("data", "tp"))


def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """``"2x4"`` -> ``(data=2, tp=4)``; a bare ``"4"`` means ``(4, 1)``."""
    s = spec.strip().lower()
    parts = s.split("x")
    if len(parts) == 1:
        parts = [parts[0], "1"]
    if len(parts) != 2:
        raise ValueError(f"mesh spec {spec!r}: expected 'DATAxTP'")
    try:
        data, tp = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"mesh spec {spec!r}: expected 'DATAxTP'") from None
    if data < 1 or tp < 1:
        raise ValueError(f"mesh spec {spec!r}: axes must be >= 1")
    return data, tp


# TPU v5e-class hardware constants used by the roofline (DESIGN.md §2)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-direction per chip, 1 axis)
DCN_BW = 6.25e9  # bytes/s per chip cross-pod (50 Gbit)
