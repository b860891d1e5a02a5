"""AOT warm-up for the serving tier: bucket table + compiled-executable cache.

Lazy ``jax.jit`` pays its compile on the first *request* — the worst place:
TTFT for the unlucky prompt length includes a full XLA compile, and every
distinct prompt length is its own unlucky prompt. The AOT tier moves all of
that to engine construction:

  * :class:`BucketTable` — a small ascending set of prefill lengths. A
    prompt admits at the smallest bucket that holds it (right-padded;
    ``models.transformer.prefill_padded`` keeps the padded rows bit-exact
    and masks the pad tail dead), so the engine serves *any* prompt length
    from a handful of compiled programs. Prompts longer than the largest
    bucket fall back to an exact-length compile, counted in
    ``stats["aot_fallbacks"]``.
  * :func:`compile_cached` — ``jax.jit(...).lower(...).compile()`` keyed by
    the frozen ``(kind, cfg[, plan], shapes, mesh)`` tuple in a module-level
    cache, mirroring the engine's ``_JIT_CACHE``: reconstructing a
    ``ServeEngine`` (same config, same mesh) reuses every executable.
    Compiled executables pin their input shardings, so the mesh is part of
    the key via :func:`mesh_key`.

The engine warms the decode tick at every power-of-two chunk size up to its
horizon plus a packed admission program per (bucket, pack) pair, then serves
with ``stats["aot_hits"]`` / ``stats["aot_misses"]`` counters — a warmed
engine's steady state shows zero misses, the property BENCH_10 asserts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512)


@dataclasses.dataclass(frozen=True)
class BucketTable:
    """Ascending, de-duplicated prefill length buckets."""

    buckets: tuple[int, ...]

    def __post_init__(self):
        bs = tuple(int(b) for b in self.buckets)
        if not bs:
            raise ValueError("BucketTable needs at least one bucket")
        if any(b < 1 for b in bs):
            raise ValueError(f"bucket lengths must be positive: {bs}")
        if list(bs) != sorted(set(bs)):
            raise ValueError(f"buckets must be ascending and unique: {bs}")
        object.__setattr__(self, "buckets", bs)

    @classmethod
    def for_cache(cls, cache_len: int,
                  buckets=DEFAULT_BUCKETS) -> "BucketTable":
        """Clip a candidate set to the slot cache: buckets longer than
        ``cache_len`` can never admit (submit rejects those prompts), and an
        empty survivor set degenerates to one full-cache bucket."""
        bs = sorted({int(b) for b in buckets if 0 < int(b) <= int(cache_len)})
        return cls(tuple(bs) if bs else (int(cache_len),))

    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest bucket holding an ``n``-token prompt; an exact-boundary
        prompt (``n == bucket``) uses that bucket, not the next one. ``None``
        = longer than every bucket (exact-length fallback)."""
        for b in self.buckets:
            if n <= b:
                return b
        return None


def mesh_key(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh for executable cache keys (``None`` for
    single-host engines). Device ids are included: executables pin input
    shardings to concrete devices."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


# Compiled executables shared across engines, keyed by the frozen
# (kind, cfg[, plan], static shapes, mesh) tuple — the AOT analogue of
# engine._JIT_CACHE. An entry is a jax Compiled object: calling it never
# retraces or recompiles.
_EXEC_CACHE: dict = {}


def lookup(key: tuple):
    return _EXEC_CACHE.get(key)


def compile_cached(key: tuple, jit_fn, args: tuple, kwargs: dict, spans):
    """AOT-compile ``jit_fn`` for the concrete ``args``/``kwargs`` (their
    shapes, dtypes *and shardings* are what gets pinned) unless an
    executable is already cached under ``key``. Lowering only traces — the
    donated buffers among ``args`` are not consumed. ``spans`` (a
    :class:`repro.serve.spans.SpanRecorder`) times the two halves as
    ``lower`` and ``compile`` (the latter a load where JAX's persistent
    cache holds the program)."""
    exe = _EXEC_CACHE.get(key)
    if exe is None:
        with spans.span("lower"):
            lowered = jit_fn.lower(*args, **kwargs)
        with spans.span("compile"):
            exe = lowered.compile()
        _EXEC_CACHE[key] = exe
    return exe


def clear_cache() -> None:
    """Drop every cached executable (tests; never needed in serving)."""
    _EXEC_CACHE.clear()


def call_matched(exe, args: tuple, kwargs: dict):
    """Call a compiled executable, re-placing any input whose sharding no
    longer matches what the executable was compiled with (a Compiled object
    rejects mismatched inputs instead of resharding them the way ``jit``
    would). Steady state is a fixed point — the engine warms with the same
    shardings the programs emit — so the device_put is a no-op almost
    always; the count of actual re-placements comes back for
    ``stats["aot_reshards"]``."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    want = jax.tree.leaves(exe.input_shardings)
    moved = 0
    if len(want) == len(leaves):
        out = []
        for x, s in zip(leaves, want):
            if isinstance(x, jax.Array) and not x.sharding.is_equivalent_to(
                    s, x.ndim):
                x = jax.device_put(x, s)
                moved += 1
            out.append(x)
        args, kwargs = jax.tree.unflatten(treedef, out)
    return exe(*args, **kwargs), moved


def pack_sizes(max_pack: int, slots: int) -> tuple[int, ...]:
    """Powers of two up to ``min(max_pack, slots)`` — the packed-admission
    group sizes the engine compiles (a group of e.g. 5 admits as 4 + 1)."""
    cap = max(1, min(int(max_pack), int(slots)))
    out = [1]
    while out[-1] * 2 <= cap:
        out.append(out[-1] * 2)
    return tuple(out)


def compile_count(table: "BucketTable", max_pack: int, slots: int,
                  horizon: int) -> int:
    """How many programs a full warm-up compiles (bucket x pack grid plus
    the power-of-two tick chunks) — surfaced by the CLI so operators can
    see what construction will pay before it happens."""
    ticks = len([s for s in _pow2_upto(horizon)])
    return len(table.buckets) * len(pack_sizes(max_pack, slots)) + ticks


def _pow2_upto(n: int):
    s = 1
    while s <= max(1, int(n)):
        yield s
        s *= 2


def tick_chunk_sizes(horizon: int) -> tuple[int, ...]:
    """The engine quantizes tick chunks to powers of two <= horizon."""
    return tuple(_pow2_upto(horizon))
