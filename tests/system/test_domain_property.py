"""Out-of-domain semantics of every ``DEFAULT_LIBRARY_KINDS`` table
(ISSUE 7 satellite): for inputs outside a table's certified domain the
datapath must either *clamp* — bit-identically across the per-table glue,
the library-bound glue and the fused backend's pointwise path — or *raise*
through ``GuardedNumerics(strict=True)``. It must never silently wrap a
code into the ROM and decode an unrelated row.

(The fused backend's softmax/rmsnorm composites are exempt from bitwise
comparison by design — their code derivation differs by up to one table
ulp, see ``FusedInterpNumerics`` — but their pointwise table entry points
are the inherited library glue and must agree exactly.)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import DEFAULT_LIBRARY_KINDS, default_explorer
from repro.core.funcspec import ACT_HI, ACT_LO
from repro.numerics import ops as nops
from repro.numerics.guard import DomainViolation, GuardedNumerics
from repro.numerics.ops import FusedInterpNumerics, InterpNumerics

ACT_KINDS = ("gelu", "sigmoid", "silu", "softplus", "tanh")
PER_TABLE = {"gelu": nops.approx_gelu, "sigmoid": nops.approx_sigmoid,
             "silu": nops.approx_silu, "softplus": nops.approx_softplus,
             "tanh": nops.approx_tanh}


@pytest.fixture(scope="module")
def lib():
    return default_explorer().compile()


def _paths(lib, kind):
    """The three float entry points for one kind: per-table glue, library
    glue, fused-backend (inherited library glue for pointwise ops)."""
    plain, fused = InterpNumerics(lib), FusedInterpNumerics(lib)
    if kind == "exp2neg":
        return (nops.approx_exp_neg, plain.exp_neg, fused.exp_neg)
    if kind == "recip":
        return (nops.approx_recip_pos, plain.recip_pos, fused.recip_pos)
    if kind == "rsqrt":
        return (nops.approx_rsqrt_pos, plain.rsqrt_pos, fused.rsqrt_pos)
    return (PER_TABLE[kind], getattr(plain, kind), getattr(fused, kind))


def _assert_paths_agree(lib, kind, x):
    a, b, c = (np.asarray(p(jnp.asarray(x, jnp.float32)), np.float32)
               for p in _paths(lib, kind))
    np.testing.assert_array_equal(a, b, err_msg=f"{kind}: per-table vs library")
    np.testing.assert_array_equal(b, c, err_msg=f"{kind}: library vs fused")
    return a


# ------------------------------------------------- example-based (always run)

def test_every_default_kind_covered():
    assert set(("exp2neg", "recip", "rsqrt") + ACT_KINDS) == set(
        DEFAULT_LIBRARY_KINDS)


@pytest.mark.parametrize("kind", ACT_KINDS)
def test_activation_out_of_window_clamps_to_tails(lib, kind):
    """Finite inputs past the table window take the certified tail values —
    identical across all three paths, saturating, never wrapped."""
    x = np.array([ACT_LO - 100.0, ACT_LO, -1.0, 0.0, 1.0, ACT_HI - 1e-3,
                  ACT_HI, ACT_HI + 100.0], np.float32)
    y = _assert_paths_agree(lib, kind, x)
    assert np.all(np.isfinite(y))
    top = 1.0 if kind in ("sigmoid", "tanh") else x[-1]
    bot = -1.0 if kind == "tanh" else 0.0
    assert y[-1] == np.float32(top)  # right tail: identity (or 1)
    assert y[0] == np.float32(bot)  # left tail: saturates to 0 (or -1)
    # saturation, not modular wrap: deep out-of-window equals the edge tail
    assert y[0] == np.asarray(PER_TABLE[kind](
        jnp.asarray([ACT_LO - 1e6], jnp.float32)), np.float32)[0]


def test_exp_neg_positive_input_clamps_to_one(lib):
    """exp2neg's domain is x <= 0; positive inputs clamp to exp(0) — the
    glue's max(-x, 0) — and deeply negative inputs underflow to 0, never
    wrapping around the exponent table."""
    x = np.array([-500.0, -126.0, -3.0, 0.0, 1.0, 700.0], np.float32)
    y = _assert_paths_agree(lib, "exp2neg", x)
    assert np.all(np.isfinite(y)) and np.all(y >= 0.0)
    assert y[3] == y[4] == y[5]  # every x >= 0 pins to the x=0 value
    assert y[0] <= 2.0 ** -120  # deep negative: underflow, not wrap


@pytest.mark.parametrize("kind", ["recip", "rsqrt"])
def test_positive_domain_extremes_agree_across_paths(lib, kind):
    from repro.numerics.guard import _POS_HUGE, _POS_TINY

    x = np.array([_POS_TINY, 1e-12, 0.5, 1.0, 2.0, 3.0, 4.0, 1e12,
                  _POS_HUGE], np.float32)
    y = _assert_paths_agree(lib, kind, x)
    # recip of the domain ceiling lands subnormal and flushes to 0 — a
    # saturated answer, still never a wrapped code
    assert np.all(np.isfinite(y)) and np.all(y >= 0.0)
    assert np.all(y[:-1] > 0.0)


@pytest.mark.parametrize("kind", ["recip", "rsqrt"])
def test_nonpositive_input_raises_through_strict_guard(lib, kind):
    """The positive-domain tables have NO certified meaning at x <= 0 (frexp
    yields garbage codes): strict GuardedNumerics refuses instead of
    wrapping."""
    g = GuardedNumerics(InterpNumerics(lib), strict=True)
    op = g.recip_pos if kind == "recip" else g.rsqrt_pos
    for bad in (0.0, -1.0, np.nan, np.inf, -np.inf):
        with pytest.raises(DomainViolation):
            op(jnp.asarray([bad], jnp.float32))
    assert g.total_violations() == 5


@pytest.mark.parametrize("kind", ["recip", "rsqrt"])
def test_guard_clamp_equals_unguarded_on_clamped_input(lib, kind):
    """Non-strict guard semantics: a bad input evaluates exactly as the
    nearest in-domain input would through the unguarded path — a bounded
    wrong answer, bit-identical to the clamp, never a wrapped code."""
    from repro.numerics.guard import _POS_HUGE, _POS_TINY

    g = GuardedNumerics(InterpNumerics(lib))
    plain = InterpNumerics(lib)
    gop = getattr(g, f"{kind}_pos")
    pop = getattr(plain, f"{kind}_pos")
    bad = np.array([0.0, -5.0, np.inf, -np.inf, np.nan, 2.0], np.float32)
    clamped = np.array([_POS_TINY, _POS_TINY, _POS_HUGE, _POS_TINY, 1.0, 2.0],
                       np.float32)
    np.testing.assert_array_equal(
        np.asarray(gop(jnp.asarray(bad)), np.float32),
        np.asarray(pop(jnp.asarray(clamped)), np.float32))
    assert g.violations[f"{kind}_pos"] == 5


@pytest.mark.parametrize("kind", ACT_KINDS)
def test_guard_repairs_nonfinite_activations(lib, kind):
    g = GuardedNumerics(InterpNumerics(lib))
    x = np.array([np.nan, np.inf, -np.inf, 1.0], np.float32)
    y = np.asarray(getattr(g, kind)(jnp.asarray(x)), np.float32)
    assert np.all(np.isfinite(y))
    assert g.violations[kind] == 3
    # the healthy element is untouched by the repair
    ref = np.asarray(getattr(InterpNumerics(lib), kind)(
        jnp.asarray([1.0], jnp.float32)), np.float32)
    assert y[3] == ref[0]


# -------------------------------------------------- property-based (hypothesis)

@settings(max_examples=25, deadline=None)
@given(st.sampled_from(ACT_KINDS),
       st.lists(st.floats(-1e6, 1e6, width=32), min_size=1, max_size=64))
def test_activation_paths_bitwise_everywhere(kind, xs):
    library = default_explorer().compile()
    _assert_paths_agree(library, kind, np.array(xs, np.float32))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(-1e4, 1e4, width=32), min_size=1, max_size=64))
def test_exp_neg_paths_bitwise_everywhere(xs):
    library = default_explorer().compile()
    y = _assert_paths_agree(library, "exp2neg", np.array(xs, np.float32))
    assert np.all(y >= 0.0)


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(["recip", "rsqrt"]),
       st.lists(st.floats(np.float32(1e-30), np.float32(1e30), width=32),
                min_size=1, max_size=64))
def test_positive_domain_paths_bitwise_everywhere(kind, xs):
    library = default_explorer().compile()
    y = _assert_paths_agree(library, kind, np.array(xs, np.float32))
    assert np.all(np.isfinite(y))


@settings(max_examples=25, deadline=None)
@given(st.sampled_from(["recip", "rsqrt"]),
       st.floats(np.float32(-1e30), 0.0, width=32))
def test_nonpositive_never_silently_wraps(kind, bad):
    """Any non-positive float either raises (strict guard) or, unguarded +
    non-strict-guarded, never produces a value that looks like a valid
    in-domain evaluation of some wrapped code — the guard pins it to the
    domain-edge result."""
    library = default_explorer().compile()
    g = GuardedNumerics(InterpNumerics(library), strict=True)
    op = g.recip_pos if kind == "recip" else g.rsqrt_pos
    with pytest.raises(DomainViolation):
        op(jnp.asarray([np.float32(bad)], jnp.float32))
