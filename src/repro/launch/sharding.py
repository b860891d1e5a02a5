"""Logical-axis sharding: rule engine mapping named tensor axes to mesh axes.

Models annotate activations with *logical* names (``constrain(x, ("batch",
"seq", "heads", None))``); a thread-local rule set maps those names onto
physical mesh axes (DP/TP/EP/SP), checking divisibility so e.g. 8 KV heads
never get forced onto a 16-way axis (they fall back to the next candidate or
to replication). Outside an active rule context ``constrain`` is a no-op, so
the same model code runs in single-device smoke tests and 512-chip dry-runs.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical axis -> ordered mesh-axis candidates (first divisible one wins).
# Training meshes name their TP axis "model"; the serving mesh
# (launch.mesh.make_serve_mesh) names it "tp" — both appear as candidates so
# the same model annotations resolve on either without a separate rule set.
DEFAULT_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    "batch": (("pod", "data"), ("data",)),
    "seq": (("model",),),  # sequence parallelism (long-context fallback)
    "heads": (("model",), ("tp",)),
    "kv_heads": (("model",), ("tp",)),
    "embed": (),  # activations replicated along d_model by default
    "mlp": (("model",), ("tp",)),
    "vocab": (("model",), ("tp",)),
    "expert": (("model",), ("tp",)),
    "kv_seq": (("model",),),  # decode KV cache sequence axis
}


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: dict | None = None):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _state.ctx = prev


def active_mesh() -> Optional[Mesh]:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def _resolve(name: Optional[str], size: int, mesh: Mesh, rules: dict,
             taken: set[str]) -> Optional[tuple[str, ...]]:
    if name is None:
        return None
    for cand in rules.get(name, ()):
        if any(ax in taken or ax not in mesh.shape for ax in cand):
            continue
        total = 1
        for ax in cand:
            total *= mesh.shape[ax]
        if size % total == 0 and size > 0:
            return cand
    return None


def logical_spec(names: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh, rules: dict) -> P:
    taken: set[str] = set()
    out = []
    for name, size in zip(names, shape):
        axes = _resolve(name, int(size), mesh, rules, taken)
        if axes is None:
            out.append(None)
        else:
            taken.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def constrain(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    """Attach a logical sharding constraint; no-op without an active mesh."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_spec(names, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def rule_spec(names: Sequence[Optional[str]], shape: Sequence[int]) -> P:
    """``logical_spec`` under the active rule context (``P()`` outside)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return P()
    mesh, rules = ctx
    return logical_spec(names, shape, mesh, rules)


def local_map(fn, args: tuple, in_specs: tuple, out_specs):
    """Run ``fn(*args)`` once per device of the active mesh on its local
    shards (``jax.shard_map``), for bodies GSPMD cannot partition — Mosaic
    kernels, which are opaque custom calls. ``in_specs`` / ``out_specs``
    are PartitionSpecs (pytree prefixes allowed, e.g. ``P()`` for a whole
    replicated library); the caller picks specs under which ``fn`` is
    independent per shard. Without an active multi-device mesh this is
    just ``fn(*args)``. The body runs with no rule context, so nothing
    inside it re-enters ``local_map`` or ``constrain``."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None or ctx[0].size == 1:
        return fn(*args)
    _state.ctx = None
    try:
        return jax.shard_map(fn, mesh=ctx[0], in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)
    finally:
        _state.ctx = ctx


def named_sharding(names: Sequence[Optional[str]], shape: Sequence[int],
                   mesh: Mesh, rules: dict | None = None) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(names, shape, mesh, rules or DEFAULT_RULES))


# ---------------------------------------------------------------------------
# parameter / state sharding (name-based Megatron TP x FSDP rules)
# ---------------------------------------------------------------------------

# logical parameter axes; resolution falls back left-to-right per candidate
PARAM_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    "tp": (("model",), ("tp",)),         # Megatron column/row axis
    "fsdp": (("pod", "data"), ("data",)),  # ZeRO-3 shard of the other big axis
    "expert": (("model",), ("tp",)),     # expert parallelism
    "vocab": (("model",), ("tp",)),
}

# Serving-tier parameter rules (DESIGN.md §17): weights tensor-parallel over
# the serve mesh's "tp" axis, *replicated* over "data". The training "fsdp"
# rule would ZeRO-shard weights over the data axis and pay a per-layer
# all-gather on every decode tick — batch slots are the data-parallel unit
# when serving, not parameters.
SERVE_PARAM_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    "tp": (("tp",), ("model",)),
    "fsdp": (),
    "expert": (("tp",), ("model",)),
    "vocab": (("tp",), ("model",)),
}

# leaf-name suffix -> logical axes for the *trailing* dims (stacked layer
# dims are padded with None on the left automatically)
_COL = ("fsdp", "tp")   # (d_in, d_out) column-parallel: shard d_out
_ROW = ("tp", "fsdp")   # (d_in, d_out) row-parallel: shard d_in
_PARAM_AXES: tuple[tuple[str, tuple], ...] = (
    ("embed/tok", ("vocab", "fsdp")),
    ("embed/head", ("fsdp", "vocab")),
    ("projector/w1", _COL), ("projector/w2", _ROW),
    ("mixer/wq", _COL), ("mixer/wk", _COL), ("mixer/wv", _COL),
    ("mixer/wo", _ROW),
    ("cross/wq", _COL), ("cross/wk", _COL), ("cross/wv", _COL), ("cross/wo", _ROW),
    ("wq_a", _COL), ("wq_b", _COL), ("wkv_a", _COL), ("wkv_b", _COL),
    ("ffn/wi", _COL), ("ffn/wo", _ROW),
    ("shared_wi", _COL), ("shared_wo", _ROW),
    ("router", ("fsdp", None)),
    ("in_proj", _COL), ("out_proj", _ROW),
    ("conv_w", (None, "tp")), ("conv_b", ("tp",)),
    ("pos", (None, "fsdp")),
)
# MoE expert tensors — layout is divisibility-adaptive (perf iterations
# A1/A4 in EXPERIMENTS.md §Perf):
#   * E % model_axis == 0 (deepseek 64, jamba 16): classic expert
#     parallelism — experts sharded on `model`, each expert dense locally.
#   * otherwise (mixtral 8 on a 16-way axis): intra-expert Megatron col/row —
#     d_expert on `model`, d_model on FSDP, experts replicated. The naive
#     expert-dim rule here replicated the dispatch buffers (measured 2.9e13
#     collective bytes/chip/step before the rewrite).
# (A4 — true expert-dim EP for divisible E — was tried and REFUTED: GSPMD
# partitions the data-dependent dispatch scatter/combine gather against an
# expert-sharded buffer with full per-layer gathers; measured 12x collective
# blow-up on deepseek/jamba train. Intra-expert TP is universal here.)
_MOE_TP = {"ffn/wi": (None, "fsdp", "tp"), "ffn/wo": (None, "tp", "fsdp")}


def param_logical_axes(name: str, ndim: int, shape: tuple = (),
                       mesh: Optional[Mesh] = None) -> tuple:
    for suffix, axes in _PARAM_AXES:
        if suffix in name:
            if suffix in _MOE_TP and ndim >= 3:
                cand = _MOE_TP[suffix]
                if ndim in (3, 4):  # maybe scan-stacked
                    axes3 = cand if ndim == 3 else (None, *cand)
                    return axes3
            pad = ndim - len(axes)
            if pad < 0:
                return (None,) * ndim
            return (None,) * pad + tuple(axes)
    return (None,) * ndim  # norms, biases, scalars: replicated


def param_specs(shapes: dict, mesh: Mesh, rules: dict | None = None) -> dict:
    """ShapeDtypeStruct tree -> NamedSharding tree (same structure)."""
    rules = rules or PARAM_RULES
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        names = param_logical_axes(name, len(leaf.shape), tuple(leaf.shape), mesh)
        out.append(NamedSharding(mesh, logical_spec(names, leaf.shape, mesh, rules)))
    return jax.tree.unflatten(treedef, out)


def batch_specs(batch_shapes: dict, mesh: Mesh, rules: dict | None = None) -> dict:
    """Input batch: leading axis is the global batch -> DP axes."""
    r = dict(DEFAULT_RULES)
    r.update(rules or {})

    def one(s):
        names = ("batch",) + (None,) * (len(s.shape) - 1)
        return NamedSharding(mesh, logical_spec(names, s.shape, mesh, r))

    return jax.tree.map(one, batch_shapes)


def cache_specs_sharding(cache_shapes: dict, cfg, mesh: Mesh) -> dict:
    """KV caches: batch->data; kv-heads->model if divisible, else the cache
    sequence axis (sequence parallelism for long-context decode).

    Field layouts (a leading scan-stacked layer dim may be prepended):
      GQA:  k,v (B, KV, S, D)   pos (B, S)
      MLA:  k (B, S, lora)  v (B, S, rope)  pos (B, S)
      SSM:  conv (B, K, C)  ssm (B, H, P, N)
    """
    kv_base = 3 if cfg.mla is not None else 4

    def one_leaf(path, s):
        field = str(path[-1]).lstrip(".")
        nd = len(s.shape)
        base = {"k": kv_base, "v": kv_base, "pos": 2, "conv": 3, "ssm": 4}[field]
        stacked = nd == base + 1
        if field in ("k", "v"):
            names = (("batch", "kv_heads", "kv_seq", None) if kv_base == 4
                     else ("batch", "kv_seq", None))
        elif field == "pos":
            names = ("batch", "kv_seq")
        elif field == "conv":
            names = ("batch", None, "tp")
        else:  # ssm state
            names = ("batch", "heads", None, None)
        if stacked:
            names = (None, *names)
        rules = dict(DEFAULT_RULES)
        rules["tp"] = (("model",), ("tp",))
        return NamedSharding(mesh, logical_spec(names, s.shape, mesh, rules))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree.unflatten(treedef, [one_leaf(p, s) for p, s in flat])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
