"""Compiled interpolation libraries: the runtime-side artifact of a session.

The paper's deployable product is not one table but the *set* of certified
piecewise-polynomial designs a model's numerics touch. ``InterpLibrary``
packs that set into a single frozen, registered JAX pytree:

  * one padded ``(F, R_max, 3)`` int32 coefficient ROM — the only dynamic
    leaf, so the artifact shards (replicated), donates, and rides inside a
    params/cache pytree through ``jit`` / ``vmap`` / ``repro.checkpoint``;
  * a tuple of static :class:`FuncMeta` records (hashable — jit treats the
    library's structure as compile-time constant): per-function widths,
    datapath shifts, and the input-window/output-span constants the float
    glue in ``repro.numerics`` needs.

Evaluation is fused: element ``i`` reads function ``fids[i]``'s rows, so
softmax's exp+recip, rmsnorm's rsqrt and the activations all lower to the
same ``(shapes, F, R_max)`` Pallas executable instead of one specialization
per table (``repro.kernels.interp``). The per-table path remains the
bit-exactness oracle. ``save``/``load`` (npz + json manifest) let a served
model start from a library with zero exploration calls. DESIGN.md §10.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
from typing import Iterable, Sequence

import numpy as np

from repro.core.funcspec import ACT_HI, ACT_KINDS, ACT_LO, act_out_span
from repro.core.table import TableDesign

# The library manifest: every table kind the interp numerics backend can
# touch at runtime (softmax exp/recip, rmsnorm rsqrt, all activations).
# ``Explorer.compile()`` defaults to this set — serving warm-up compiles it
# once instead of hand-maintaining a per-engine kind list.
DEFAULT_LIBRARY_KINDS = ("exp2neg", "gelu", "recip", "rsqrt", "sigmoid",
                         "silu", "softplus", "tanh")

# Manifest format: version 1 is the uniform layout (rows [0, 2^R) of a slot
# hold packed coeffs). Version 2 adds non-uniform segmentation (ISSUE 8 /
# DESIGN.md §15): a segmented slot stores S per-leaf coefficient rows
# followed by the segment-index table packed 3 int32 entries per row; the
# per-leaf datapath lives in FuncMeta.seg_meta. A library with no segmented
# function still saves as version 1, so v1 artifacts round-trip byte- and
# checksum-identically through this code.
_FORMAT_VERSION = 1
_FORMAT_VERSION_SEG = 2


class LibraryIntegrityError(RuntimeError):
    """The resident ROM no longer matches the checksum it was sealed with.

    Raised by :meth:`InterpLibrary.verify_resident` — the serve-time
    counterpart of the load-time ``coeffs_sha`` check: a bit flipped in the
    in-memory coefficient ROM *after* a clean load (DMA corruption, a rogue
    write, an injected fault) is caught here instead of silently decoding
    garbage through every fused kernel that gathers the ROM.
    """


@dataclasses.dataclass(frozen=True)
class FuncMeta:
    """Static per-function metadata of one library slot (hashable)."""

    kind: str  # registry kind, e.g. "exp2neg" — the numerics lookup key
    name: str  # design name, e.g. "exp2neg_12"
    in_bits: int
    out_bits: int
    lookup_bits: int  # R: this function uses rows [0, 2^R) of its slot
    k: int
    degree: int
    sq_trunc: int
    lin_trunc: int
    act_lo: float = 0.0  # input window (direct activation tables only)
    act_hi: float = 0.0
    act_span: float = 0.0  # output span S: float value = int * S / 2^out_bits
    # non-uniform segmentation (ROM v2; 0/() = uniform): seg_depth is the
    # segment-index table depth D (the top D input bits address the table),
    # seg_meta holds one (eval_bits, k, sq_trunc, lin_trunc, degree) row per
    # leaf. For a segmented slot the scalar k/degree/truncation fields above
    # record leaf 0's values and lookup_bits records D.
    seg_depth: int = 0
    seg_meta: tuple = ()

    @property
    def eval_bits(self) -> int:
        return self.in_bits - self.lookup_bits

    @property
    def segmented(self) -> bool:
        return self.seg_depth > 0

    @property
    def rows_used(self) -> int:
        """Slot rows this function occupies: 2^R uniform, else the per-leaf
        coefficient rows plus the packed segment-index table rows."""
        if not self.seg_depth:
            return 1 << self.lookup_bits
        return len(self.seg_meta) + ((1 << self.seg_depth) + 2) // 3

    def seg_spec(self) -> tuple | None:
        """Static segment-datapath tuple the fused kernels consume
        (``None`` = uniform): (in_bits, depth, n_leaves, leaf_meta)."""
        if not self.seg_depth:
            return None
        return (self.in_bits, self.seg_depth, len(self.seg_meta),
                self.seg_meta)

    def datapath_row(self) -> tuple[int, int, int, int, int]:
        """The (eval_bits, k, sq_trunc, lin_trunc, degree) kernel row."""
        return (self.eval_bits, self.k, self.sq_trunc, self.lin_trunc,
                self.degree)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if not self.seg_depth:  # keep uniform manifests byte-stable with v1
            d.pop("seg_depth")
            d.pop("seg_meta")
        else:
            d["seg_meta"] = [list(row) for row in self.seg_meta]
        return d


def _meta_from_dict(d: dict) -> FuncMeta:
    """Rebuild a FuncMeta from a manifest entry (v1 entries carry no seg
    fields; v2 seg_meta arrives as JSON lists and must re-freeze to nested
    tuples so the dataclass stays hashable)."""
    d = dict(d)
    if "seg_meta" in d:
        d["seg_meta"] = tuple(tuple(int(v) for v in row)
                              for row in d["seg_meta"])
    return FuncMeta(**d)


class InterpLibrary:
    """Frozen pytree of every table a model's numerics touch.

    Construct through :meth:`from_designs` / :meth:`repro.api.Explorer.
    compile` / :meth:`load`; the raw constructor is the pytree-unflatten
    hook and performs no validation (leaves may be tracers).
    """

    __slots__ = ("coeffs", "metas", "_index", "_meta_rows", "_walk_rows",
                 "_sealed_sha")

    def __init__(self, coeffs, metas: tuple[FuncMeta, ...]):
        self.coeffs = coeffs  # (F, R_max, 3) int32 — the only dynamic leaf
        self.metas = tuple(metas)
        self._index = {m.kind: i for i, m in enumerate(self.metas)}
        self._meta_rows = None  # lazy (F, 5) device array
        self._walk_rows = None  # lazy ((F, 5), (L, 5)) walk/datapath arrays
        self._sealed_sha = None  # integrity baseline (seal/verify_resident)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_designs(cls, designs: Sequence[TableDesign],
                     kinds: Sequence[str],
                     act_windows: dict | None = None) -> "InterpLibrary":
        """Pack verified designs into one padded ROM + static metadata.

        ``act_windows``: optional ``{kind: (lo, hi)}`` for activation tables
        generated over a non-default input window — recorded in the metadata
        and honored by the library-bound float glue.
        """
        import jax.numpy as jnp

        assert len(designs) == len(kinds) and len(designs) > 0
        dupes = {k for k in kinds if list(kinds).count(k) > 1}
        if dupes:  # _index would silently shadow the earlier slot
            raise ValueError(f"duplicate kinds in library: {sorted(dupes)}")
        metas = []
        for kind, d in zip(kinds, designs):
            seg_depth = getattr(d, "seg_depth", 0)
            if not seg_depth and d.degree != 2 and np.any(d.a != 0):
                raise ValueError(  # fused path zeroes the squarer by degree
                    f"{d.name}: degree-{d.degree} design with nonzero a")
            act = kind in ACT_KINDS
            lo, hi = (act_windows or {}).get(kind, (ACT_LO, ACT_HI))
            metas.append(FuncMeta(
                kind=kind, name=d.name, in_bits=d.in_bits,
                out_bits=d.out_bits, lookup_bits=d.lookup_bits, k=d.k,
                degree=d.degree, sq_trunc=d.sq_trunc, lin_trunc=d.lin_trunc,
                act_lo=lo if act else 0.0, act_hi=hi if act else 0.0,
                act_span=act_out_span(kind, lo, hi) if act else 0.0,
                seg_depth=seg_depth,
                seg_meta=tuple(getattr(d, "leaf_meta", ()))))
        r_max = max(m.rows_used for m in metas)
        packed = np.zeros((len(designs), r_max, 3), np.int32)
        for i, (m, d) in enumerate(zip(metas, designs)):
            packed[i, : m.rows_used] = d.packed_coeffs()
        return cls(jnp.asarray(packed), tuple(metas)).seal()

    # -- introspection -----------------------------------------------------
    @property
    def kinds(self) -> tuple[str, ...]:
        return tuple(m.kind for m in self.metas)

    @property
    def r_max(self) -> int:
        return max(m.rows_used for m in self.metas)

    @property
    def segmented_kinds(self) -> tuple[str, ...]:
        return tuple(m.kind for m in self.metas if m.seg_depth)

    def __contains__(self, kind: str) -> bool:
        return kind in self._index

    def __len__(self) -> int:
        return len(self.metas)

    def __repr__(self) -> str:
        return (f"InterpLibrary({len(self.metas)} funcs, "
                f"coeffs{tuple(np.shape(self.coeffs))}: "
                f"{', '.join(self.kinds)})")

    def func_id(self, kind: str) -> int:
        try:
            return self._index[kind]
        except KeyError:
            raise KeyError(f"{kind!r} not in library {self.kinds}") from None

    def meta(self, kind: str) -> FuncMeta:
        return self.metas[self.func_id(kind)]

    def meta_rows(self):
        """(F, 5) int32 device array of datapath rows (kernel operand)."""
        import jax
        import jax.numpy as jnp

        if self._meta_rows is None:
            rows = jnp.asarray(
                np.array([m.datapath_row() for m in self.metas], np.int32))
            if isinstance(rows, jax.core.Tracer):
                # jnp.asarray returns a tracer under an active trace even
                # for a concrete constant; caching one would leak it
                return rows
            self._meta_rows = rows
        return self._meta_rows

    def walk_rows(self):
        """Operands of the generalized multi-function ROM walk: a ``(F, 5)``
        int32 walk table of ``(in_bits, depth, seg_flag, leaf_base,
        n_leaves)`` rows — depth is R for a uniform slot, the segment-index
        depth D for a segmented one — plus an ``(L, 5)`` datapath table with
        one ``(eval_bits, k, sq_trunc, lin_trunc, degree)`` row per uniform
        function and one per segmented leaf (``leaf_base`` indexes it)."""
        import jax
        import jax.numpy as jnp

        if self._walk_rows is None:
            walk, dp = [], []
            for m in self.metas:
                base = len(dp)
                if m.seg_depth:
                    walk.append((m.in_bits, m.seg_depth, 1, base,
                                 len(m.seg_meta)))
                    dp.extend(m.seg_meta)
                else:
                    walk.append((m.in_bits, m.lookup_bits, 0, base, 1))
                    dp.append(m.datapath_row())
            rows = (jnp.asarray(np.array(walk, np.int32)),
                    jnp.asarray(np.array(dp, np.int32)))
            if any(isinstance(r, jax.core.Tracer) for r in rows):
                return rows  # see meta_rows: never cache a traced constant
            self._walk_rows = rows
        return self._walk_rows

    # -- integrity ---------------------------------------------------------
    def rom_sha(self) -> str:
        """Checksum of the ROM bits actually resident right now (downloads
        the coefficient leaf; host-side only — never call under a trace)."""
        coeffs = np.asarray(self.coeffs, np.int32)
        return hashlib.sha256(
            np.ascontiguousarray(coeffs).tobytes()).hexdigest()[:16]

    def seal(self, sha: str | None = None) -> "InterpLibrary":
        """Record the integrity baseline ``verify_resident`` checks against
        (the current resident checksum, or a known-good one from a saved
        manifest). Construction paths seal automatically; returns self."""
        self._sealed_sha = sha or self.rom_sha()
        return self

    @property
    def sealed_sha(self) -> str | None:
        return self._sealed_sha

    def verify_resident(self) -> str:
        """Re-checksum the in-memory ROM against the sealed baseline.

        This is the *serve-time* integrity guard (DESIGN.md §14): ``load``
        already rejects a corrupt artifact, but a post-load bit flip in the
        resident device buffer is invisible to that check. An unsealed
        library (pytree round-trips drop the baseline) is sealed on first
        verify. Returns the verified checksum; raises
        :class:`LibraryIntegrityError` on mismatch.
        """
        sha = self.rom_sha()
        if self._sealed_sha is None:
            self._sealed_sha = sha
        elif sha != self._sealed_sha:
            raise LibraryIntegrityError(
                f"resident ROM checksum {sha} != sealed {self._sealed_sha}: "
                f"the in-memory coefficient ROM was corrupted after load")
        return sha

    def manifest(self) -> dict:
        f, r_max, _ = np.shape(self.coeffs)
        version = (_FORMAT_VERSION_SEG if any(m.seg_depth for m in self.metas)
                   else _FORMAT_VERSION)
        return {
            "version": version,
            "kinds": list(self.kinds),
            "n_funcs": int(f),
            "r_max": int(r_max),
            "funcs": [m.to_dict() for m in self.metas],
        }

    # -- evaluation --------------------------------------------------------
    def eval_int(self, codes, kind: str, use_kernel: bool | None = None,
                 interpret: bool | None = None):
        """Exact integer evaluation of one function (static kind).

        ``use_kernel=None`` picks the fused Pallas kernel on TPU and the
        jnp slice path elsewhere; both are bit-identical to the per-table
        ``table_eval_int`` oracle (tests/api/test_library.py).
        """
        import jax

        from repro.kernels.interp.ops import _on_tpu
        from repro.kernels.interp.ref import interp_eval_ref

        fid = self.func_id(kind)
        m = self.metas[fid]
        if use_kernel or (use_kernel is None and _on_tpu()):
            return self.eval_fused(codes, fid, use_kernel=True,
                                   interpret=interpret)
        rows = jax.lax.index_in_dim(self.coeffs, fid, 0, keepdims=False)
        if m.seg_depth:
            # jnp path of a non-uniform slot: the segment-index gather
            # oracle (bit-identical to the in-kernel walk)
            from repro.kernels.interp.ref import interp_eval_seg_ref

            return interp_eval_seg_ref(codes, rows, seg=m.seg_spec())
        return interp_eval_ref(
            codes, rows[: 1 << m.lookup_bits], eval_bits=m.eval_bits,
            k=m.k, sq_trunc=m.sq_trunc, lin_trunc=m.lin_trunc,
            degree=m.degree)

    def eval_fused(self, codes, fids, use_kernel: bool = True,
                   interpret: bool | None = None):
        """Fused multi-function evaluation: element i reads table fids[i].

        Serves any mix of uniform (v1) and segmented (v2) slots. An
        all-uniform library keeps the original (F, 5)-meta fast path —
        byte-stable programs for v1 artifacts — while the presence of any
        segmented slot switches the call onto the generalized ROM walk
        (``library_walk``): per-function walk rows plus per-leaf datapath
        rows as kernel operands, same ROM selects and fixed-point
        tail, bit-identical per slot to the specialized paths.
        """
        if any(m.seg_depth for m in self.metas):
            from repro.kernels.interp.ops import library_walk

            walk, dp = self.walk_rows()
            return library_walk(codes, fids, self.coeffs, walk, dp,
                                use_kernel=use_kernel, interpret=interpret)
        from repro.kernels.interp.ops import library_eval

        return library_eval(codes, fids, self.coeffs, self.meta_rows(),
                            use_kernel=use_kernel, interpret=interpret)

    # -- persistence (npz coefficients + json manifest) --------------------
    def save(self, path: str | pathlib.Path) -> pathlib.Path:
        """Write the ROM npz + ``<path>.json`` manifest; returns the
        manifest path. A saved library serves with zero exploration.

        A crash mid-save can never tear an artifact — not even a re-save
        over an existing one: the ROM is written to a tmp path and renamed
        to a *content-addressed* name (``<path>.<sha>.npz``, which the
        manifest references), then the manifest is atomically replaced. At
        every instant the on-disk json points at a complete ROM whose
        checksum matches. Superseded ROM files are unlinked only after the
        new manifest is in place (best-effort).
        """
        base = pathlib.Path(path)
        if base.suffix in (".json", ".npz"):
            base = base.with_suffix("")
        base.parent.mkdir(parents=True, exist_ok=True)
        coeffs = np.asarray(self.coeffs, np.int32)
        sha = hashlib.sha256(
            np.ascontiguousarray(coeffs).tobytes()).hexdigest()[:16]
        npz_path = base.parent / f"{base.name}.{sha}.npz"
        tmp_npz = npz_path.with_suffix(".npz.tmp")
        try:
            with open(tmp_npz, "wb") as f:
                np.savez(f, coeffs=coeffs)
            tmp_npz.replace(npz_path)
        finally:
            tmp_npz.unlink(missing_ok=True)
        man = self.manifest()
        man["coeffs_file"] = npz_path.name
        man["coeffs_sha"] = sha
        tmp = base.with_suffix(".json.tmp")
        tmp.write_text(json.dumps(man, indent=1))
        tmp.replace(base.with_suffix(".json"))
        for stale in base.parent.glob(f"{base.name}.*.npz"):
            if stale.name != npz_path.name:
                stale.unlink(missing_ok=True)
        return base.with_suffix(".json")

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "InterpLibrary":
        import jax.numpy as jnp

        base = pathlib.Path(path)
        if base.suffix in (".json", ".npz"):
            base = base.with_suffix("")
        man = json.loads(base.with_suffix(".json").read_text())
        if man.get("version") not in (_FORMAT_VERSION, _FORMAT_VERSION_SEG):
            raise ValueError(f"unsupported library version {man.get('version')}")
        with np.load(base.parent / man["coeffs_file"]) as z:
            coeffs = z["coeffs"].astype(np.int32)
        sha = hashlib.sha256(
            np.ascontiguousarray(coeffs).tobytes()).hexdigest()[:16]
        if man.get("coeffs_sha") and sha != man["coeffs_sha"]:
            raise ValueError(f"corrupt library ROM {base}.npz")
        metas = tuple(_meta_from_dict(f) for f in man["funcs"])
        return cls(jnp.asarray(coeffs), metas).seal(sha)


def load_library(path: str | pathlib.Path) -> InterpLibrary:
    """Module-level convenience: :meth:`InterpLibrary.load`."""
    return InterpLibrary.load(path)


def _flatten_with_keys(lib: InterpLibrary):
    import jax

    return ((jax.tree_util.GetAttrKey("coeffs"), lib.coeffs),), lib.metas


def _flatten(lib: InterpLibrary):
    return (lib.coeffs,), lib.metas


def _unflatten(metas, leaves) -> InterpLibrary:
    return InterpLibrary(leaves[0], metas)


def _register() -> None:
    import jax

    jax.tree_util.register_pytree_with_keys(
        InterpLibrary, _flatten_with_keys, _unflatten, _flatten)


_register()
