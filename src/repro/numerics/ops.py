"""JAX evaluation of generated tables + approximate transcendental ops.

This is the integration layer between the paper's artifacts and the model
stack: pure-jnp (GSPMD-shardable) implementations of softmax / rsqrt / SiLU /
exp built on the certified piecewise-polynomial tables. The Pallas kernels in
``repro.kernels`` fuse the same math for the hot paths; these functions are
their reference semantics and the portable fallback used inside the large
models (so the multi-pod dry-run lowers identically on any backend).

Float glue (max-subtract, exponent split, power-of-two scaling) is exact
hardware-wise — only the table lookups carry approximation error, and those
errors are *proved* bounds from table verification.

Since ISSUE 3 the backends are *instances*: ``get_numerics(cfg)`` returns an
object, and the interp backend can be bound to a compiled
:class:`repro.api.InterpLibrary` so every lookup resolves against one packed
artifact (no process-global registry on the hot path). Unbound instances
fall back to the default Explorer session, preserving the legacy behavior.
The float glue is shared between the per-table and library paths — the two
differ only in who evaluates the integer table, which is exactly the part
the golden tests pin bit-for-bit.
"""
from __future__ import annotations

import contextlib
import math
import threading
from functools import partial

import jax
import jax.numpy as jnp

from repro.api import get_table
from repro.core.funcspec import ACT_HI, ACT_LO, act_out_span
from repro.core.table import TableDesign

LOG2E = 1.4426950408889634

# Trace-time sinks for fused-attention sites: a stats dict registered by
# count_attention_sites() (the serving engine wraps every trace of its
# programs in one) gets ATTN_FALLBACK_KEY incremented each time a traced
# attention takes the chunked glue path instead of the fused kernel, and
# ATTN_FOLD_KEY each time a traced fused attention folds a group's query
# heads into the rows of one tile per kv stripe, ATTN_ABSORB_KEY each
# time an MLA decode attention is traced in the absorbed-latent form, and
# CACHE_INPLACE_KEY each time a scanned decode segment is traced with its
# stacked caches carried through the layer loop (written in place).
ATTN_FALLBACK_KEY = "attn_glue_fallbacks"
ATTN_FOLD_KEY = "attn_folded_sites"
ATTN_ABSORB_KEY = "attn_absorbed_sites"
CACHE_INPLACE_KEY = "cache_inplace_sites"
_SINKS = threading.local()


@contextlib.contextmanager
def count_attention_sites(sink: dict):
    """Count, into ``sink``, every fused-attention refusal
    (``ATTN_FALLBACK_KEY``), folded flash call (``ATTN_FOLD_KEY``),
    absorbed MLA decode attention (``ATTN_ABSORB_KEY``) and scanned decode
    segment carrying its cache stack (``CACHE_INPLACE_KEY``) traced inside
    the block (the count is per trace: a program that is
    already compiled is not traced again)."""
    stack = getattr(_SINKS, "stack", None)
    if stack is None:
        stack = _SINKS.stack = []
    stack.append(sink)
    try:
        yield sink
    finally:
        stack.pop()


def note_attention_site(key: str) -> None:
    """Count one traced site under ``key`` in every active sink."""
    for sink in getattr(_SINKS, "stack", ()):
        sink[key] = sink.get(key, 0) + 1


def table_eval_int(codes: jax.Array, design: TableDesign) -> jax.Array:
    """Evaluate a table on int32 input codes (exact integer semantics).

    Designs whose coefficients exceed int32 route to the emulated-int64
    path (DESIGN.md §7.5) instead of silently wrapping through the int32
    device cache."""
    if not design.fits_int32:
        from repro.kernels.interp.ref import interp_eval_wide

        return interp_eval_wide(codes, design.device_coeffs_wide(),
                                eval_bits=design.eval_bits, k=design.k,
                                sq_trunc=design.sq_trunc,
                                lin_trunc=design.lin_trunc,
                                degree=design.degree)
    w = design.eval_bits
    coeffs = design.device_coeffs()
    r = jax.lax.shift_right_logical(codes, w)
    x = jnp.bitwise_and(codes, (1 << w) - 1)
    sel = coeffs[r]  # gather: (..., 3)
    xs = jax.lax.shift_left(jax.lax.shift_right_logical(x, design.sq_trunc), design.sq_trunc)
    xl = jax.lax.shift_left(jax.lax.shift_right_logical(x, design.lin_trunc), design.lin_trunc)
    acc = sel[..., 0] * xs * xs + sel[..., 1] * xl + sel[..., 2]
    return jax.lax.shift_right_arithmetic(acc, design.k)


def _quantize(v: jax.Array, bits: int) -> jax.Array:
    """Map v in [0, 1) to an input code (round-to-nearest, clamped)."""
    q = jnp.round(v * (1 << bits)).astype(jnp.int32)
    return jnp.clip(q, 0, (1 << bits) - 1)


# ---------------------------------------------------------------------------
# float glue, parameterized over the integer table evaluator. ``ev`` maps
# int32 codes to the table's integer output; in_bits/out_bits come from the
# design or the library metadata. Exactly one implementation of each glue
# exists, so the per-table and library-bound paths cannot drift.
# ---------------------------------------------------------------------------

def _exp_neg_glue(x, in_bits: int, out_bits: int, ev) -> jax.Array:
    """exp(x) for x <= 0:  2^(x*log2e) = 2^(-n) * tab(-f)."""
    t = jnp.maximum(-x, 0.0).astype(jnp.float32) * LOG2E
    t = jnp.minimum(t, 126.0)  # below fp32 denormal cliff anyway
    n = jnp.floor(t)
    f = t - n  # in [0, 1)
    codes = _quantize(f, in_bits)
    frac = ev(codes).astype(jnp.float32) * (2.0 ** -out_bits)
    return frac * jnp.exp2(-n)  # exp2 of an integer == exact exponent shift


def _recip_pos_glue(x, in_bits: int, ev) -> jax.Array:
    """1/(m * 2^e) = recip(m) * 2^-e,  m in [1, 2)."""
    m, e = jnp.frexp(x.astype(jnp.float32))  # m in [0.5, 1)
    m2 = 2.0 * m  # [1, 2)
    codes = _quantize(m2 - 1.0, in_bits)
    # table target: V = 2^(2b+1)/(2^b + Z)  ==  (1/m2) * 2^(bits+1)
    val = ev(codes).astype(jnp.float32) * (2.0 ** -(in_bits + 1))
    return val * jnp.exp2(1.0 - e.astype(jnp.float32))  # 1/x = (1/m2) * 2^(1-e)


def _rsqrt_pos_glue(x, in_bits: int, out_bits: int, ev) -> jax.Array:
    """x = v * 4^h, v in [1,4);  rsqrt = tab(v) * 2^-h."""
    m, e = jnp.frexp(x.astype(jnp.float32))  # x = m * 2^e, m in [0.5, 1)
    e = e.astype(jnp.int32)
    odd = jnp.bitwise_and(e, 1)  # e odd -> v = m*2 in [1,2); even -> v = m*4 in [2,4)
    v = jnp.where(odd == 1, 2.0 * m, 4.0 * m)
    h = jnp.where(odd == 1, (e - 1) // 2, (e - 2) // 2)
    half = 1 << (in_bits - 1)
    codes = jnp.where(
        odd == 1,
        _quantize(v - 1.0, in_bits - 1),
        half + _quantize((v - 2.0) * 0.5, in_bits - 1),
    ).astype(jnp.int32)
    codes = jnp.clip(codes, 0, (1 << in_bits) - 1)
    val = ev(codes).astype(jnp.float32) * (2.0 ** -out_bits)
    return val * jnp.exp2(-h.astype(jnp.float32))


def _range_glue(x, in_bits: int, out_bits: int, span: float, ev,
                lo: float = ACT_LO, hi: float = ACT_HI) -> jax.Array:
    """Direct table over [lo, hi): quantize the window, rescale the output."""
    xc = jnp.clip(x.astype(jnp.float32), lo, hi - 1e-6)
    codes = _quantize((xc - lo) / (hi - lo), in_bits)
    return ev(codes).astype(jnp.float32) * (span / (1 << out_bits))


def _act_tails(kind: str, x, y, lo: float = ACT_LO, hi: float = ACT_HI):
    """Outside the table window the activations are linear (right tail) or
    saturate; sigmoid saturates to 1/0, tanh to 1/-1, the rest to x/0."""
    top = 1.0 if kind in ("sigmoid", "tanh") else x
    bot = -1.0 if kind == "tanh" else 0.0
    return jnp.where(x >= hi, top, jnp.where(x <= lo, bot, y)).astype(x.dtype)


# ---------------------------------------------------------------------------
# per-table entry points (design argument; default = the process session).
# These remain the bit-exactness oracle for the library-fused path.
# ---------------------------------------------------------------------------

def _tab(kind: str, design: TableDesign | None) -> TableDesign:
    return design if design is not None else get_table(kind)


def approx_exp_neg(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    """exp(x) for x <= 0 via the exp2neg table; exact power-of-two scaling."""
    d = _tab("exp2neg", design)
    return _exp_neg_glue(x, d.in_bits, d.out_bits, lambda c: table_eval_int(c, d))


def approx_recip_pos(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    d = _tab("recip", design)
    return _recip_pos_glue(x, d.in_bits, lambda c: table_eval_int(c, d))


def approx_rsqrt_pos(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    d = _tab("rsqrt", design)
    return _rsqrt_pos_glue(x, d.in_bits, d.out_bits, lambda c: table_eval_int(c, d))


def _approx_act(kind: str, x: jax.Array, design: TableDesign | None) -> jax.Array:
    d = _tab(kind, design)
    y = _range_glue(x, d.in_bits, d.out_bits, act_out_span(kind),
                    lambda c: table_eval_int(c, d))
    return _act_tails(kind, x, y)


def approx_silu(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    return _approx_act("silu", x, design)


def approx_sigmoid(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    return _approx_act("sigmoid", x, design)


def approx_softplus(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    return _approx_act("softplus", x, design)


def approx_gelu(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    return _approx_act("gelu", x, design)


def approx_tanh(x: jax.Array, design: TableDesign | None = None) -> jax.Array:
    return _approx_act("tanh", x, design)


# ---------------------------------------------------------------------------
# composite ops
# ---------------------------------------------------------------------------

def approx_softmax(x: jax.Array, axis: int = -1,
                   exp_design: TableDesign | None = None,
                   recip_design: TableDesign | None = None) -> jax.Array:
    """Softmax with table-backed exponential and normalization reciprocal."""
    xf = x.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(xf, axis=axis, keepdims=True))
    e = approx_exp_neg(xf - m, exp_design)
    s = jnp.sum(e, axis=axis, keepdims=True)
    return (e * approx_recip_pos(s, recip_design)).astype(x.dtype)


def approx_rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
                   design: TableDesign | None = None) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    return (xf * approx_rsqrt_pos(var, design) * gamma).astype(x.dtype)


# ---------------------------------------------------------------------------
# numerics backends handed to the model stack
# ---------------------------------------------------------------------------

class ExactNumerics:
    """Plain XLA transcendentals (the no-technique baseline)."""

    name = "exact"
    library = None

    softmax = staticmethod(jax.nn.softmax)
    silu = staticmethod(jax.nn.silu)
    gelu = staticmethod(partial(jax.nn.gelu, approximate=True))
    sigmoid = staticmethod(jax.nn.sigmoid)
    softplus = staticmethod(jax.nn.softplus)
    tanh = staticmethod(jnp.tanh)

    @staticmethod
    def exp_neg(x):
        return jnp.exp(x)

    @staticmethod
    def rmsnorm(x, gamma, eps=1e-6):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
        return (xf * jax.lax.rsqrt(var) * gamma).astype(x.dtype)

    @staticmethod
    def recip_pos(x):
        return 1.0 / x


class InterpNumerics:
    """The paper's technique as the model's numerics backend.

    An instance optionally binds a compiled :class:`repro.api.InterpLibrary`
    — then every table lookup evaluates through the library's packed ROM
    (one artifact, no registry, fused Pallas kernel on TPU) and the instance
    never calls the default Explorer. Unbound (``library=None``, the legacy
    behavior and the ``get_numerics("interp")`` default) each op resolves
    its table lazily through ``repro.api.get_table``.
    """

    name = "interp"

    def __init__(self, library=None):
        self.library = library

    def _ev(self, kind: str):
        """(in_bits, out_bits, int-evaluator) for ``kind``."""
        lib = self.library
        if lib is not None:
            m = lib.meta(kind)  # KeyError = artifact missing a used kind
            return m.in_bits, m.out_bits, lambda c: lib.eval_int(c, kind)
        d = get_table(kind)
        return d.in_bits, d.out_bits, lambda c: table_eval_int(c, d)

    def exp_neg(self, x):
        ib, ob, ev = self._ev("exp2neg")
        return _exp_neg_glue(x, ib, ob, ev)

    def recip_pos(self, x):
        ib, _, ev = self._ev("recip")
        return _recip_pos_glue(x, ib, ev)

    def rsqrt_pos(self, x):
        ib, ob, ev = self._ev("rsqrt")
        return _rsqrt_pos_glue(x, ib, ob, ev)

    def _act(self, kind: str, x):
        lib = self.library
        if lib is not None:
            # the artifact records the window the table was generated over —
            # honor it (a custom-window library must not quantize over the
            # defaults)
            m = lib.meta(kind)
            y = _range_glue(x, m.in_bits, m.out_bits, m.act_span,
                            lambda c: lib.eval_int(c, kind),
                            m.act_lo, m.act_hi)
            return _act_tails(kind, x, y, m.act_lo, m.act_hi)
        ib, ob, ev = self._ev(kind)
        return _act_tails(kind, x, _range_glue(x, ib, ob, act_out_span(kind), ev))

    def silu(self, x):
        return self._act("silu", x)

    def sigmoid(self, x):
        return self._act("sigmoid", x)

    def softplus(self, x):
        return self._act("softplus", x)

    def gelu(self, x):
        return self._act("gelu", x)

    def tanh(self, x):
        return self._act("tanh", x)

    def softmax(self, x, axis: int = -1):
        xf = x.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(xf, axis=axis, keepdims=True))
        e = self.exp_neg(xf - m)
        s = jnp.sum(e, axis=axis, keepdims=True)
        return (e * self.recip_pos(s)).astype(x.dtype)

    def rmsnorm(self, x, gamma, eps: float = 1e-6):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
        return (xf * self.rsqrt_pos(var) * gamma).astype(x.dtype)


class FusedInterpNumerics(InterpNumerics):
    """Library-bound interp numerics with fused-kernel lowering.

    Same certified tables, different datapath: softmax, rmsnorm and the
    attention inner loop lower to the library-bound fused kernels
    (``kernels/{softmax,rmsnorm,flashattn}``) — the ROM gather and the
    fixed-point Horner evaluation happen *inside* the consuming kernel, so
    a decode layer is O(1) kernel launches instead of a gather→eval→
    elementwise chain per transcendental. Off-TPU the same ops run through
    the fused jnp oracles (bit-identical integer datapath, identical glue).

    Float-level caveat: the fused reciprocal/rsqrt glue derives table codes
    by IEEE-754 bit twiddles where the unfused glue uses ``frexp`` — the
    int datapath is bit-identical (golden-tested per kind against
    ``table_eval_int``), but composite float outputs may differ by one
    table ulp from :class:`InterpNumerics`. The engine-level oracles
    therefore compare fused-vs-fused runs.
    """

    name = "interp"
    fused = True

    def __init__(self, library):
        if library is None:
            raise ValueError(
                "FusedInterpNumerics needs a compiled InterpLibrary: the "
                "fused kernels thread its ROM as an operand (compile one "
                "with Explorer.compile() or pass fused=False)")
        super().__init__(library)

    def softmax(self, x, axis: int = -1):
        if axis not in (-1, x.ndim - 1):
            return super().softmax(x, axis=axis)
        # local import: kernels.flashattn.ref imports this module
        from repro.kernels.softmax.ops import approx_softmax_library

        return approx_softmax_library(x, self.library).astype(x.dtype)

    def rmsnorm(self, x, gamma, eps: float = 1e-6):
        from repro.kernels.rmsnorm.ops import approx_rmsnorm_library

        return approx_rmsnorm_library(x, gamma, self.library,
                                      eps=eps).astype(x.dtype)

    def fused_attention(self, q, k, v, q_pos, kv_pos, *, causal, window,
                        scale, q_rope=None, k_rope=None):
        """The ``attention_core`` fast path: whole-datapath flash attention
        with the library ROM inlined; the absorbed MLA form (``q_rope``,
        ``k_rope``, values = keys) routes to its latent kernel. Returns
        None (caller falls back to the chunked glue path) when the layout
        is unsupported; each refusal is counted into the active
        ``count_attention_sites`` sink."""
        from repro.kernels.flashattn.ops import attention_fused_library

        b, sq, h, d = q.shape
        kvh = k.shape[2]
        # the kernel holds the whole K/V stripe per program (the flashattn
        # VMEM bound): longer contexts keep the chunked memory-bounded glue
        # path on every backend. Off-TPU the oracle materializes the (N,
        # Sq, Sk) score block, so long-context prefill stays on the glue
        # path there.
        if (h % kvh or k.shape[1] > 4096
                or (sq * k.shape[1] > (1 << 22)
                    and jax.default_backend() != "tpu")):
            note_attention_site(ATTN_FALLBACK_KEY)
            return None
        # grouped kv heads pass through unexpanded: one program per kv
        # stripe (group folded into its rows) or per query head
        return attention_fused_library(q, k, v, self.library, causal=causal,
                                       window=window, scale=scale,
                                       q_pos=q_pos, kv_pos=kv_pos,
                                       q_rope=q_rope, k_rope=k_rope)


BACKENDS = {"exact": ExactNumerics, "interp": InterpNumerics,
            "interp-fused": FusedInterpNumerics}

INTERP_BACKENDS = ("interp", "interp-fused", "interp-guarded")


def get_numerics(cfg_or_name="exact", library=None, fused: bool = False):
    """Resolve a numerics backend *instance* for a model config (or a plain
    backend name). ``library`` binds the interp backend to a compiled
    :class:`repro.api.InterpLibrary`; the exact backend gets the trivial
    instance (no tables to bind). ``fused=True`` (or the explicit
    ``"interp-fused"`` name) selects the fused-kernel lowering — softmax /
    rmsnorm / attention evaluate the library ROM *inside* the consuming
    kernel; it requires a bound library. ``"interp-guarded"`` is the
    degraded-mode backend (DESIGN.md §14): the same per-table interp
    datapath behind the :class:`repro.numerics.guard.GuardedNumerics`
    domain clamp.

    A config carrying a :class:`repro.plan.NumericsPlan` resolves to a
    :class:`repro.plan.numerics.PlanNumerics` instead — per-layer x per-site
    backends; ``fused`` is then ignored (each site assignment names its own
    lowering) and ``library`` may be a dict keyed by plan slot."""
    plan = getattr(cfg_or_name, "plan", None)
    if plan is not None:
        from repro.plan.numerics import plan_numerics

        return plan_numerics(plan, libraries=library)
    name = getattr(cfg_or_name, "numerics", cfg_or_name)
    if name == "exact":
        return ExactNumerics()
    if name == "interp-guarded":
        from repro.numerics.guard import GuardedNumerics

        return GuardedNumerics(InterpNumerics(library))
    if name == "interp-fused" or (name == "interp" and fused):
        return FusedInterpNumerics(library)
    if name == "interp":
        return InterpNumerics(library)
    raise KeyError(f"unknown numerics backend {name!r}")


def softmax_ulp_bound(exp_design=None, recip_design=None) -> float:
    """Certified relative error bound of approx_softmax terms, from the
    tables' verified ULP guarantees (used by tests and EXPERIMENTS.md).
    Accepts ``TableDesign`` or library ``FuncMeta`` (only widths are read);
    ``None`` resolves through the default session."""
    exp_design = exp_design or get_table("exp2neg")
    recip_design = recip_design or get_table("recip")
    # quantization of f adds 1/2 ulp of 2^-in_bits in the exponent argument
    exp_rel = (2.0 ** -exp_design.out_bits) * 2 + math.log(2.0) * 2.0 ** -(exp_design.in_bits + 1)
    recip_rel = 2.0 ** -recip_design.in_bits  # quantization + 1 ulp of output
    return 2 * exp_rel + 2 * recip_rel
