"""CPU rehearsal of ``chip_smoke.py``: its phases run at smoke size (Pallas
kernels in interpret mode) imported as functions and pass; the script
itself refuses to report success off a TPU, and when it stands alone
without the repository."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SCRIPT = ROOT / "chip_smoke.py"
SMOKE = dict(n_layers=None, smoke_model=True, slots=4, cache_len=64,
             requests=4, prompt_lens=(5, 24), max_new=4, buckets=(16, 32),
             max_pack=2, horizon=2)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve via sys.modules
    spec.loader.exec_module(mod)
    return mod


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_one_chip_phases_pass_at_smoke_size(cs, tmp_path, capsys):
    cs.run(1, seed=0, sizes=cs.Sizes(**SMOKE), interpret=True,
           table_dir=tmp_path / "tables")
    out = capsys.readouterr().out
    assert "bitwise equal to table_eval_int" in out
    assert "[interp-fused] served 4 requests" in out
    # the library was generated into the fresh cache, not read elsewhere
    assert sorted(p.name for p in (tmp_path / "tables").iterdir())


def test_kernel_check_catches_a_corrupt_rom(cs, tmp_path):
    """A flipped ROM word must fail the bitwise check, not pass it."""
    import jax.numpy as jnp

    sizes = cs.Sizes(**SMOKE)
    ex, lib = cs.fresh_library(tmp_path / "tables")
    lib.coeffs = lib.coeffs.at[0, 3, 2].add(1)
    with pytest.raises(cs.CheckFailed, match="ROM read"):
        cs.check_kernels(ex, lib, cs.model_config(sizes), sizes, seed=0,
                         interpret=True)
    assert jnp.asarray(lib.coeffs).dtype == jnp.int32


def test_mesh_phase_passes_on_four_virtual_devices(tmp_path):
    code = f"""
import importlib.util, pathlib, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
cs = importlib.util.module_from_spec(spec); sys.modules["chip_smoke"] = cs
spec.loader.exec_module(cs)
sys.path.insert(0, {str(ROOT / 'src')!r})
import jax
assert len(jax.devices()) == 4, jax.devices()
cs.run(4, seed=0, sizes=cs.Sizes(**{SMOKE!r}), interpret=True,
       table_dir=pathlib.Path({str(tmp_path / 'tables')!r}))
print("MESH-PHASE-OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "MESH-PHASE-OK" in out.stdout
    assert "first-step logits, sharded vs single" in out.stdout


def test_script_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, env=env, timeout=300, cwd=str(ROOT))
    assert out.returncode != 0
    last = _last_json(out.stdout)
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_script_alone_without_the_repository_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, env=env, timeout=300,
                         cwd=str(tmp_path))
    assert out.returncode != 0
    assert _last_json(out.stdout)["ok"] is False
