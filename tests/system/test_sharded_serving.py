"""Sharded serving tier (DESIGN.md §17). Each case runs in a subprocess
with ``--xla_force_host_platform_device_count=8`` so the rest of the suite
keeps seeing one device (per the dry-run isolation rule).

The contract: a ``("data", "tp")``-meshed engine — KV pool batch-sharded
over data and head-sharded over tp, weights TP-sharded, ROM replicated —
emits **bitwise** the token streams of the single-host engine, on the
exact path and under a uniform interp-fused :class:`NumericsPlan`; ROM
verification and the degradation ladder keep working on sharded state.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

SRC = str(pathlib.Path(__file__).resolve().parents[2] / "src")


def _run(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        assert len(jax.devices()) == 8
        from repro.configs.base import get_smoke_config
        from repro.launch.mesh import make_serve_mesh
        from repro.models import transformer as tf
        from repro.serve.engine import Request, ServeEngine

        def serve(cfg, params, prompts, **kw):
            eng = ServeEngine(cfg, params, slots=4, cache_len=48, **kw)
            for i, p in enumerate(prompts):
                eng.submit(Request(i, p, max_new=5))
            out = {r.rid: tuple(r.out) for r in eng.run()}
            eng.close()
            return out, eng

        cfg = get_smoke_config("yi_6b")
        params = tf.init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 11, 3, 16, 9, 2)]
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_meshed_engine_bitwise_exact_path():
    _run("""
    ref, _ = serve(cfg, params, prompts)
    for data, tp in ((2, 1), (1, 2), (2, 2), (4, 2)):
        got, eng = serve(cfg, params, prompts,
                         mesh=make_serve_mesh(data, tp),
                         aot_buckets=(8, 16), async_host=True)
        assert got == ref, f"{data}x{tp} diverged"
        assert eng.stats["aot_misses"] == 0, eng.stats
        assert eng.stats["aot_hits"] > 0, eng.stats
    print("exact OK")
    """)


def test_meshed_engine_bitwise_uniform_plan():
    _run("""
    from repro.plan.schema import SlotSpec, plan_for
    cfgp = cfg.replace(plan=plan_for(cfg, backend="interp-fused",
                                     slot=SlotSpec(lookup_bits=6)))
    ref, leg = serve(cfgp, params, prompts)
    got, eng = serve(cfgp, params, prompts, library=leg.library,
                     mesh=make_serve_mesh(2, 2), aot_buckets=(8, 16))
    assert got == ref, "uniform-plan mesh engine diverged"
    print("plan OK")
    """)


def test_rom_verify_and_degradation_on_sharded_state():
    _run("""
    import dataclasses
    from repro.faults import flip_rom_bit

    cfg_i = dataclasses.replace(cfg, numerics="interp")
    ref, leg = serve(cfg_i, params, prompts)
    # periodic verification passes on the replicated ROM
    got, eng = serve(cfg_i, params, prompts, library=leg.library,
                     mesh=make_serve_mesh(2, 2), verify_rom_every=2,
                     aot_buckets=(8, 16))
    assert got == ref
    assert eng.stats["rom_verifies"] >= 1, eng.stats
    assert eng.stats["rom_faults"] == 0

    # a corrupt replicated ROM is detected and the ladder degrades —
    # the engine still finishes every request on sharded state
    eng2 = ServeEngine(cfg_i, params, slots=4, cache_len=48,
                       library=leg.library, mesh=make_serve_mesh(2, 2),
                       verify_rom_every=1)
    eng2.library = flip_rom_bit(eng2.library, seed=9)
    for i, p in enumerate(prompts):
        eng2.submit(Request(i, p, max_new=5))
    done = eng2.run()
    assert eng2.stats["rom_faults"] >= 1, eng2.stats
    assert eng2.stats["degradations"] >= 1, eng2.stats
    assert len(done) == len(prompts)
    print("rom OK")
    """)


def test_mesh_factory_validation():
    _run("""
    from repro.launch.mesh import parse_mesh_spec
    assert parse_mesh_spec("2x4") == (2, 4)
    assert parse_mesh_spec("4") == (4, 1)
    for bad in ("", "0x2", "2x", "axb"):
        try:
            parse_mesh_spec(bad)
        except ValueError:
            pass
        else:
            raise AssertionError(f"{bad!r} accepted")
    m = make_serve_mesh(2, 2)
    assert m.axis_names == ("data", "tp")
    assert m.devices.shape == (2, 2)
    try:
        make_serve_mesh(8, 2)  # 16 > 8 devices
    except ValueError:
        pass
    else:
        raise AssertionError("oversized mesh accepted")

    # the kernels' SPMD contract: a partitioned ROM operand is refused
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels.interp.ops import assert_rom_replicated
    rom = np.zeros((8, 4, 3), np.int32)
    assert_rom_replicated(jax.device_put(rom, NamedSharding(m, P())))
    try:
        assert_rom_replicated(jax.device_put(rom, NamedSharding(m, P("data"))))
    except ValueError:
        pass
    else:
        raise AssertionError("partitioned ROM accepted")
    print("factory OK")
    """)


def test_fused_kernels_run_per_device_on_a_mesh():
    """On a mesh the Pallas library kernels (opaque to the SPMD
    partitioner on a TPU) run per device on their shards through
    ``local_map``: rmsnorm / softmax rows, flash attention batch rows and
    kv-head groups (GQA), elementwise ROM walks. Interpret-mode kernels
    under ``jit`` on sharded inputs equal the single-device kernels
    bitwise."""
    _run("""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.api import default_explorer
    from repro.kernels.flashattn.ops import attention_fused_library
    from repro.kernels.interp.ops import library_walk
    from repro.kernels.rmsnorm.ops import approx_rmsnorm_library
    from repro.kernels.softmax.ops import approx_softmax_library
    from repro.launch import sharding as shlib

    lib = default_explorer().compile()
    walk, dp = lib.walk_rows()
    kw = dict(use_kernel=True, interpret=True)
    r = np.random.default_rng(0)
    b, s, h, kvh, d = 4, 16, 8, 2, 64
    q = jnp.asarray(r.normal(0, 1, (b, s, h, d)), jnp.float32)
    k = jnp.asarray(r.normal(0, 1, (b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(r.normal(0, 1, (b, s, kvh, d)), jnp.float32)
    x = jnp.asarray(r.normal(0, 2, (b, s, 200)), jnp.float32)
    gamma = jnp.asarray(r.normal(1, 0.1, 200), jnp.float32)
    codes = jnp.asarray(r.integers(0, 4096, (b, s, 256)), jnp.int32)
    fids = jnp.asarray(r.integers(0, len(lib.kinds), (b, s, 256)), jnp.int32)

    def f(q, k, v, x, codes, fids):
        return (attention_fused_library(q, k, v, lib, causal=True, **kw),
                approx_rmsnorm_library(x, gamma, lib, **kw),
                approx_softmax_library(x, lib, **kw),
                library_walk(codes, fids, lib.coeffs, walk, dp, **kw))

    want = jax.jit(f)(q, k, v, x, codes, fids)
    for data, tp in ((2, 2), (4, 2), (1, 2)):
        mesh = make_serve_mesh(data, tp)
        put = lambda a, names: jax.device_put(
            a, shlib.named_sharding(names, a.shape, mesh))
        args = (put(q, ("batch", None, "heads", None)),
                put(k, ("batch", None, "kv_heads", None)),
                put(v, ("batch", None, "kv_heads", None)),
                put(x, ("batch", None, None)),
                put(codes, ("batch", None, "mlp")),
                put(fids, ("batch", None, "mlp")))
        with shlib.axis_rules(mesh):
            text = jax.jit(f).lower(*args).as_text()
            got = jax.jit(f)(*args)
        assert "shard_map" in text or "manual" in text, (data, tp)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    print("OK")
    """)
