"""Resolve a :class:`NumericsPlan` into executable backend objects.

The model stack consumes one ``numerics`` object per layer; a plan engine
holds a :class:`PlanNumerics`, asks it ``for_layer(i)`` inside
``apply_segment`` and gets either a raw homogeneous backend (when all three
op sites of the layer agree — the bitwise-identity path) or a
:class:`SiteNumerics` that routes each op family to its site's backend.
``PlanNumerics`` itself answers every op by delegating to the ``rest``
assignment, so call sites outside the layer stack (final norm, encoder,
projector) need no plan awareness.

Backends and per-layer wrappers are interned per distinct assignment, so
two layers with equal assignments share one instance — ``apply_segment``
groups consecutive equal layers by identity and scans each group once.
"""
from __future__ import annotations

from typing import Optional

from repro.plan.schema import SITES, LayerAssign, NumericsPlan, SiteAssign

# op name -> op site; everything the model stack calls on a numerics object
SITE_OF_OP = {
    "exp_neg": "softmax", "recip_pos": "softmax", "softmax": "softmax",
    "rmsnorm": "rmsnorm", "rsqrt_pos": "rmsnorm",
    "silu": "act", "gelu": "act", "sigmoid": "act", "softplus": "act",
    "tanh": "act",
}


def _resolve_backend(assign: SiteAssign, libraries):
    """Instantiate the backend of one site assignment. ``libraries`` is a
    dict keyed by slot key, a single library applied to every slot, or
    None (per-op lazy table resolution through the default session)."""
    from repro.numerics.ops import (ExactNumerics, FusedInterpNumerics,
                                    InterpNumerics)

    if assign.backend == "exact":
        return ExactNumerics()
    if isinstance(libraries, dict):
        lib = libraries.get(assign.slot.key)
    else:
        lib = libraries
    if assign.backend == "interp":
        return InterpNumerics(lib)
    if assign.backend == "interp-guarded":
        from repro.numerics.guard import GuardedNumerics

        return GuardedNumerics(InterpNumerics(lib))
    if assign.backend == "interp-fused":
        if lib is None:
            raise ValueError(
                f"plan site {assign} is interp-fused but no library is "
                f"bound for slot {assign.slot.key!r}; compile one with "
                f"compile_plan_libraries()")
        return FusedInterpNumerics(lib)
    raise KeyError(assign.backend)


class SiteNumerics:
    """Per-op-site router: one layer's three backends behind the uniform
    numerics interface the model stack already speaks."""

    name = "plan-site"

    def __init__(self, softmax_b, rmsnorm_b, act_b):
        self._softmax = softmax_b
        self._rmsnorm = rmsnorm_b
        self._act = act_b

    @property
    def library(self):
        return self._softmax.library

    # softmax site
    def exp_neg(self, x):
        return self._softmax.exp_neg(x)

    def recip_pos(self, x):
        return self._softmax.recip_pos(x)

    def softmax(self, x, axis: int = -1):
        return self._softmax.softmax(x, axis=axis)

    def fused_attention(self, q, k, v, q_pos, kv_pos, *, causal, window,
                        scale, q_rope=None, k_rope=None):
        fa = getattr(self._softmax, "fused_attention", None)
        if fa is None:
            return None  # caller falls back to the chunked glue path
        return fa(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                  scale=scale, q_rope=q_rope, k_rope=k_rope)

    # rmsnorm site
    def rmsnorm(self, x, gamma, eps: float = 1e-6):
        return self._rmsnorm.rmsnorm(x, gamma, eps)

    def rsqrt_pos(self, x):
        return self._rmsnorm.rsqrt_pos(x)

    # activation site
    def silu(self, x):
        return self._act.silu(x)

    def gelu(self, x):
        return self._act.gelu(x)

    def sigmoid(self, x):
        return self._act.sigmoid(x)

    def softplus(self, x):
        return self._act.softplus(x)

    def tanh(self, x):
        return self._act.tanh(x)


class PlanNumerics:
    """A resolved plan: per-layer numerics plus the ``rest`` delegate."""

    name = "plan"

    def __init__(self, plan: NumericsPlan, libraries=None):
        self.plan = plan
        self.libraries = libraries
        self._backends: dict[SiteAssign, object] = {}
        self._by_layer: dict[LayerAssign, object] = {}
        self._layers = tuple(self._layer_numerics(la) for la in plan.layers)
        self._rest = self._layer_numerics(plan.rest)

    def _backend(self, assign: SiteAssign):
        b = self._backends.get(assign)
        if b is None:
            b = _resolve_backend(assign, self.libraries)
            self._backends[assign] = b
        return b

    def _layer_numerics(self, la: LayerAssign):
        n = self._by_layer.get(la)
        if n is None:
            if la.uniform_backend is not None:
                # collapsed case: the layer's three sites share one backend
                # instance — the exact program the homogeneous path builds
                n = self._backend(la.softmax)
            else:
                n = SiteNumerics(*(self._backend(la.site(s)) for s in SITES))
            self._by_layer[la] = n
        return n

    def for_layer(self, i: int):
        return self._layers[i]

    @property
    def library(self):
        return self.libraries

    def __getattr__(self, attr):
        # ops outside the layer stack (final norm, encoder, projector,
        # embeddings glue) evaluate under the ``rest`` assignment
        if attr.startswith("_"):
            raise AttributeError(attr)
        return getattr(self._rest, attr)


def compile_plan_libraries(plan: NumericsPlan, explorer=None
                           ) -> Optional[dict]:
    """One compiled :class:`InterpLibrary` per distinct slot of the plan.

    Every slot library carries the full default kind manifest (not just the
    site's kinds): a collapsed uniform layer binds a single backend serving
    all three sites, and the homogeneous engines it must match bitwise
    compile the full manifest too.
    """
    slots = plan.slots()
    if not slots:
        return None
    from repro.api import default_explorer

    ex = explorer if explorer is not None else default_explorer()
    out = {}
    for key, slot in sorted(slots.items()):
        kw = slot.table_kwargs()
        if slot.segmentation == "hier":
            out[key] = ex.compile_segmented(**kw)
        else:
            out[key] = ex.compile(**kw)
    return out


def plan_numerics(plan: NumericsPlan, libraries=None,
                  explorer=None) -> PlanNumerics:
    """Resolve a plan, compiling slot libraries when none are supplied and
    the plan has fused sites (serial interp sites can stay lazy)."""
    if libraries is None and any(
            a.backend == "interp-fused" for _, _, a in plan.assignments()):
        libraries = compile_plan_libraries(plan, explorer)
    return PlanNumerics(plan, libraries)
