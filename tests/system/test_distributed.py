"""Distributed semantics tests. Each case runs in a subprocess with
``--xla_force_host_platform_device_count=8`` so the rest of the suite keeps
seeing one device (per the dry-run isolation rule)."""
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
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    _run("""
    from repro.configs.base import get_smoke_config
    from repro.data import make_batch
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_host_mesh
    from repro.train.step import StepConfig, make_train_step, train_state_init

    cfg = get_smoke_config("yi_6b").replace(n_layers=2)
    mesh = make_host_mesh(model=2)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 32, 4).items()}
    sc = StepConfig(peak_lr=1e-3, warmup=0)
    step = make_train_step(cfg, sc)

    s0 = train_state_init(jax.random.key(0), cfg)
    _, m_single = jax.jit(step)(s0, batch, jnp.asarray(0))

    s0b = train_state_init(jax.random.key(0), cfg)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s0b)
    st_sh = shlib.param_specs(shapes, mesh)
    b_sh = shlib.batch_specs({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in batch.items()}, mesh)
    s0b = jax.tree.map(jax.device_put, s0b, st_sh)
    batch_s = jax.tree.map(jax.device_put, batch, b_sh)
    with shlib.axis_rules(mesh):
        jstep = jax.jit(step, in_shardings=(st_sh, b_sh, None),
                        out_shardings=(st_sh, None))
        _, m_shard = jstep(s0b, batch_s, jnp.asarray(0))
    np.testing.assert_allclose(float(m_single["loss"]), float(m_shard["loss"]),
                               rtol=2e-4)
    np.testing.assert_allclose(float(m_single["grad_norm"]),
                               float(m_shard["grad_norm"]), rtol=2e-3)
    print("OK sharded == single")
    """)


def test_sharded_decode_matches_single_device():
    _run("""
    from repro.configs.base import get_smoke_config
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.numerics.ops import get_numerics

    cfg = get_smoke_config("qwen1_5_110b").replace(n_layers=2)
    numerics = get_numerics("exact")
    mesh = make_host_mesh(model=4)
    params = tf.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)

    logits, caches, _ = tf.prefill(params, toks, cfg, numerics, 32)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    l_single, _ = tf.decode_step(params, tok, jnp.asarray(16, jnp.int32),
                                 caches, cfg, numerics)

    p_shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    p_sh = shlib.param_specs(p_shapes, mesh)
    c_shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), caches)
    c_sh = shlib.cache_specs_sharding(c_shapes, cfg, mesh)
    params_s = jax.tree.map(jax.device_put, params, p_sh)
    caches_s = jax.tree.map(jax.device_put, caches, c_sh)
    with shlib.axis_rules(mesh):
        fn = jax.jit(lambda p, t, q, c: tf.decode_step(p, t, q, c, cfg, numerics),
                     in_shardings=(p_sh, None, None, c_sh))
        l_shard, _ = fn(params_s, tok, jnp.asarray(16, jnp.int32), caches_s)
    np.testing.assert_allclose(np.asarray(l_single, np.float32),
                               np.asarray(l_shard, np.float32),
                               rtol=5e-3, atol=5e-3)
    print("OK decode sharded == single")
    """)


def test_fleet_front_half_sharded_matches_single_device():
    """The fleet §II front half sharded over 8 host devices (shard_map over
    the probe axis) agrees with the single-device program exactly — each
    shard runs the same per-row kernel — and with the exact numpy engine on
    every feasibility verdict."""
    _run("""
    from repro.core import batched, fleet
    from repro.core.funcspec import get_spec
    from repro.kernels.dspace.ops import fleet_region_envelopes_device

    pairs = [("recip", 8, 3), ("exp2", 8, 3), ("silu", 8, 3), ("recip", 8, 4)]
    bounds = [get_spec(k, b).region_bounds(r) for k, b, r in pairs]
    stack = fleet.stack_bounds(bounds)
    one = fleet_region_envelopes_device(stack.L, stack.U, shards=1,
                                        interpret=True)
    sh8 = fleet_region_envelopes_device(stack.L, stack.U, shards=8,
                                        interpret=True)
    # probe count (4) does not divide 8: exercises the sentinel probe pad
    for a, b in zip(one, sh8):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    spaces = fleet.fleet_region_spaces_device(stack, shards=8, interpret=True)
    for i, (L, U) in enumerate(bounds):
        exact = batched.region_spaces(L, U)
        assert [s.feasible for s in spaces[i]] == \\
            [s.feasible for s in exact], i
    print("OK fleet sharded == single == exact verdicts")
    """)


def test_elastic_reshard_roundtrip(tmp_path):
    _run(f"""
    from repro.checkpoint import save
    from repro.launch.elastic import remesh_state, reshard_checkpoint
    from repro.launch import sharding as shlib

    tree = {{"embed": {{"tok": jnp.arange(64.0).reshape(16, 4)}},
            "mixer": {{"wq": jnp.ones((8, 16))}}}}
    mesh8 = jax.make_mesh((2, 4), ("data", "model"))
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    sh8 = shlib.param_specs(shapes, mesh8)
    t8 = jax.tree.map(jax.device_put, tree, sh8)
    save(r"{tmp_path}", 3, t8)

    mesh2 = jax.make_mesh((1, 2), ("data", "model"))
    step, t2 = reshard_checkpoint(r"{tmp_path}", shapes, mesh2)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(t2["embed"]["tok"]),
                                  np.arange(64.0).reshape(16, 4))
    # and in-memory remesh back up to 8
    t8b = remesh_state(t2, mesh8)
    np.testing.assert_array_equal(np.asarray(t8b["mixer"]["wq"]), np.ones((8, 16)))
    print("OK elastic")
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
    from functools import partial
    from repro.launch.pipeline import pipeline_apply, bubble_fraction

    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = jax.make_mesh((4,), ("stage",))
    key = jax.random.key(0)
    w = jax.random.normal(key, (n_stages, d, d)) / jnp.sqrt(d)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))

    def stage_fn(p, h):
        return jnp.tanh(h @ p)

    y_pipe = pipeline_apply(w, x, stage_fn, mesh, axis="stage")

    y_ref = x
    for s in range(n_stages):
        y_ref = jnp.tanh(y_ref @ w[s])
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
    print("OK pipeline")
    """)


def test_grad_compression_pod_axis():
    _run("""
    from repro.optim.compress import compress_grads, compress_init, decompress_grads
    # pod-axis semantics: compress per shard, all-reduce int8 payloads'
    # dequantized means across a 2-pod axis == mean of raw grads (within
    # quantization error + EF residual carry)
    g_pod = [{"w": jax.random.normal(jax.random.key(i), (256,))} for i in range(2)]
    res = [compress_init(g) for g in g_pod]
    deq = []
    for g, r in zip(g_pod, res):
        payload, scales, _ = compress_grads(g, r)
        deq.append(decompress_grads(payload, scales)["w"])
    mean_q = (deq[0] + deq[1]) / 2
    mean_t = (g_pod[0]["w"] + g_pod[1]["w"]) / 2
    err = float(jnp.max(jnp.abs(mean_q - mean_t)))
    scale = float(jnp.max(jnp.abs(mean_t)))
    assert err < 0.02 * scale + 0.05, (err, scale)
    print("OK compression")
    """)
