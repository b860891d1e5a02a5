"""Placement of JAX's persistent compilation cache
(``repro.launch.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` when set
(compiled entries land there), otherwise the fixed
``<repo>/artifacts/jax_cache``. Each case runs in a fresh interpreter so
the process-wide JAX config of the suite is untouched."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import setup_compile_cache
print("DIR", setup_compile_cache())
print("CFG", jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
"""


def _probe(env_dir: str | None, compile_: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines()
                if line[:3] in ("DIR", "CFG"))


def test_env_dir_receives_the_compiled_entries(tmp_path):
    got = _probe(str(tmp_path / "cc"), compile_=True)
    assert got["DIR"] == got["CFG"] == str(tmp_path / "cc")
    assert any(p.name.endswith("-cache") for p in (tmp_path / "cc").iterdir())


def test_default_dir_is_fixed_inside_the_repo():
    got = _probe(None, compile_=False)
    want = str(ROOT / "artifacts" / "jax_cache")
    assert got["DIR"] == got["CFG"] == want
