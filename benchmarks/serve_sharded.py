"""Sharded, AOT-warmed serving tier benchmark (DESIGN.md §17) -> BENCH_10.

Sweeps batch x bucket-table x mesh over two serving modes:

  offline   MLPerf-style max-throughput: submit the whole batch up front,
            measure tokens / wall-clock from first submit to last retire.
            The baseline is the PR-5 single-host fused engine (lazy jit):
            its measured window pays one admission compile per distinct
            prompt length, exactly what AOT warm-up moves to construction.
  online    latency-SLO: per-request TTFT (submit -> first emitted token)
            p50/p99 plus attainment against a fixed SLO. A warmed engine's
            TTFT carries zero compile (asserted: ``aot_misses == 0`` and
            steady-state ``aot_hits > 0``).

Every mesh row is decoded twice more under single-host engines — exact
numerics and a uniform interp-fused :class:`NumericsPlan` — and the token
streams are asserted **bitwise identical** to the sharded run before any
row is emitted (the GSPMD partitioning and the padded-bucket prefill must
not change a single token).

This sweep is a CPU rehearsal of the mesh path, not a device
measurement: it runs in a subprocess pinned to the CPU backend
(``JAX_PLATFORMS=cpu``) with ``--xla_force_host_platform_device_count=8``
virtual devices, so it never contends for an accelerator the parent (or
anything else on the host) holds, and its times are host-CPU times. Rows
come back over stdout and land in
``artifacts/bench/serve_sharded_{offline,online}.json``, folded into
``BENCH_10.json`` by ``benchmarks.run``. The mesh path on real chips is
``python chip_smoke.py --chips 4``.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from benchmarks.common import QUICK, emit

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")
_MARK = "SERVE_SHARDED_ROWS:"

OFFLINE_COLS = ["mode", "mesh", "batch", "buckets", "tokens", "wall_s",
                "tok_s", "speedup_vs_lazy", "admit_dispatches",
                "packed_admits", "aot_hits", "aot_misses", "aot_reshards",
                "bitwise_exact", "bitwise_plan"]
ONLINE_COLS = ["mode", "mesh", "batch", "buckets", "ttft_p50_ms",
               "ttft_p99_ms", "slo_ms", "slo_attained", "tok_s",
               "aot_misses"]


def _worker() -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import time

    import jax
    import numpy as np

    from repro.configs.base import get_smoke_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import transformer as tf
    from repro.plan.schema import SlotSpec, plan_for
    from repro.serve import aot as aot_mod
    from repro.serve.engine import Request, ServeEngine

    assert len(jax.devices()) == 8, jax.devices()
    quick = os.environ.get("BENCH_QUICK", "0") == "1"

    cfg = get_smoke_config("yi_6b")
    cfg_plan = cfg.replace(plan=plan_for(cfg, backend="interp-fused",
                                         slot=SlotSpec(lookup_bits=6)))
    params = tf.init_params(jax.random.key(0), cfg)
    CACHE, MAX_NEW, SLOTS = 64, 8, 8
    BUCKETS = (8, 16, 32)
    batches = (4, 8) if quick else (4, 8, 16)
    meshes = ((1, 1), (2, 1)) if quick else ((1, 1), (2, 1), (2, 2), (4, 2))
    rng = np.random.default_rng(11)
    workloads = {b: [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
                     for n in rng.integers(3, 33, b)] for b in batches}

    def serve(engine, prompts, ttft=False):
        """Submit everything, run to drain; returns (tokens dict, wall
        seconds, per-request TTFT seconds)."""
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            engine.submit(Request(i, p, max_new=MAX_NEW))
        first: dict[int, float] = {}
        while engine.step():
            if ttft:
                now = time.perf_counter()
                for r in list(engine.req) + list(engine.finished):
                    if r is not None and r.out and r.rid not in first:
                        first[r.rid] = now - t0
        engine._drain_pipeline()
        wall = time.perf_counter() - t0
        return ({r.rid: tuple(r.out) for r in engine.finished}, wall,
                [first[k] for k in sorted(first)] if ttft else [])

    # single-host references (exact + uniform plan), lazy PR-5 baseline
    refs, ref_plan, lazy_wall = {}, {}, {}
    for b in batches:
        eng = ServeEngine(cfg, params, slots=SLOTS, cache_len=CACHE)
        refs[b], lazy_wall[b], _ = serve(eng, workloads[b])
        engp = ServeEngine(cfg_plan, params, slots=SLOTS, cache_len=CACHE)
        ref_plan[b], _, _ = serve(engp, workloads[b])

    offline, online = [], []
    slo_s = 1.0  # generous CPU-host SLO; the point is the p99 column
    for data, tp in meshes:
        mesh = make_serve_mesh(data, tp)
        name = f"{data}x{tp}"
        for b in batches:
            kw = dict(slots=SLOTS, cache_len=CACHE, mesh=mesh,
                      aot_buckets=BUCKETS, max_pack=4)
            eng = ServeEngine(cfg, params, **kw)  # warm-up outside the clock
            got, wall, _ = serve(eng, workloads[b])
            assert got == refs[b], (
                f"sharded {name} batch {b}: exact tokens diverged")
            assert eng.stats["aot_misses"] == 0, eng.stats
            assert eng.stats["aot_hits"] > 0, eng.stats
            engp = ServeEngine(cfg_plan, params, **kw)
            gotp, _, _ = serve(engp, workloads[b])
            assert gotp == ref_plan[b], (
                f"sharded {name} batch {b}: uniform-plan tokens diverged")
            tokens = sum(len(v) for v in got.values())
            offline.append({
                "mode": "offline", "mesh": name, "batch": b,
                "buckets": ",".join(map(str, BUCKETS)), "tokens": tokens,
                "wall_s": wall, "tok_s": tokens / wall,
                "speedup_vs_lazy": lazy_wall[b] / wall,
                "admit_dispatches": eng.stats["admit_dispatches"],
                "packed_admits": eng.stats["packed_admits"],
                "aot_hits": eng.stats["aot_hits"],
                "aot_misses": eng.stats["aot_misses"],
                "aot_reshards": eng.stats["aot_reshards"],
                "bitwise_exact": True, "bitwise_plan": True,
            })
            eng2 = ServeEngine(cfg, params, **kw)
            got2, wall2, ttfts = serve(eng2, workloads[b], ttft=True)
            assert got2 == refs[b]
            tokens2 = sum(len(v) for v in got2.values())
            ts = np.asarray(sorted(ttfts))
            online.append({
                "mode": "online", "mesh": name, "batch": b,
                "buckets": ",".join(map(str, BUCKETS)),
                "ttft_p50_ms": float(np.percentile(ts, 50)) * 1e3,
                "ttft_p99_ms": float(np.percentile(ts, 99)) * 1e3,
                "slo_ms": slo_s * 1e3,
                "slo_attained": float((ts <= slo_s).mean()),
                "tok_s": tokens2 / wall2,
                "aot_misses": eng2.stats["aot_misses"],
            })
        aot_mod.clear_cache()  # next mesh pins different shardings

    print(_MARK + json.dumps({"offline": offline, "online": online}))


def run() -> None:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_sharded", "--worker"],
        capture_output=True, text=True, env=env, timeout=3000,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]))
    if out.returncode != 0:
        raise RuntimeError(f"serve_sharded worker failed\nSTDOUT:\n"
                           f"{out.stdout}\nSTDERR:\n{out.stderr}")
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith(_MARK))
    rows = json.loads(line[len(_MARK):])
    emit("serve_sharded_offline", rows["offline"], OFFLINE_COLS)
    emit("serve_sharded_online", rows["online"], ONLINE_COLS)


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        run()
