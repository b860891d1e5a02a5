"""Test-suite bootstrap: run without optional dependencies.

``hypothesis`` powers the property-based tests but is an optional extra
(``pip install -e .[test]``). When it is absent we install a stub module
into ``sys.modules`` *before* test collection so the property tests are
skipped cleanly while every example-based test in the same files still
runs. The stub mirrors the handful of entry points the suite uses:
``given`` (returns a skip-marking decorator), ``settings`` (identity
decorator), and ``strategies`` (an absorbing object, since strategy
construction only happens at decoration time).
"""
from __future__ import annotations

import sys
import types

import pytest

try:
    import hypothesis  # noqa: F401
except ImportError:
    class _Absorb:
        """Callable/attribute sink standing in for the strategies module."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    def _given(*args, **kwargs):
        def deco(fn):
            return pytest.mark.skip(
                reason="hypothesis not installed (pip install -e .[test])")(fn)
        return deco

    def _settings(*args, **kwargs):
        return lambda fn: fn

    _st = _Absorb()
    stub = types.ModuleType("hypothesis")
    stub.given = _given
    stub.settings = _settings
    stub.strategies = _st
    stub.__is_repro_stub__ = True
    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.__getattr__ = lambda name: _st  # PEP 562 module-level fallback
    sys.modules["hypothesis"] = stub
    sys.modules["hypothesis.strategies"] = st_mod


@pytest.fixture(autouse=True, scope="module")
def _fresh_default_span_recorder():
    """Each test file starts from a fresh default span recorder, as a
    process of its own would: the spans of the newest engine another file
    built on the same worker never reach this file's readers."""
    spans = sys.modules.get("repro.serve.spans")
    if spans is not None:
        spans.set_default(spans.SpanRecorder())
    yield
