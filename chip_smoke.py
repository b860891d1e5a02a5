#!/usr/bin/env python3
"""Bring-up smoke test of the serving path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the (1, 4) tensor-parallel serve mesh

One chip: yi_6b at its published widths (d_model 4096, 32 heads, 4 KV
heads, head_dim 128, d_ff 11008, vocab 64000, bfloat16; random weights
from ``--seed``), depth cut to 16 of its 32 layers. The interpolation
library is compiled in-process into a fresh table cache. Before serving,
the library kernels run on the chip at decode shapes against their jnp
oracles: every kind's table read, the fused multi-function walk, the
fused softmax and rmsnorm must be bitwise equal; flash attention, whose
oracle is unchunked, must agree within the test suite's tolerance. Then the normal
``ServeEngine`` (AOT-warmed bucketed prefill, fused decode tick) serves 8
requests of 128-512 prompt tokens and 32 new tokens each, first with
``exact`` numerics and then with ``interp-fused``, and the compiled fused
tick is checked for the library kernels (``tpu_custom_call``).

``--chips 4`` runs only the same config on a ``make_serve_mesh(1, 4)``
mesh and the single-device run it is compared with.

Any failed check exits non-zero with ``"ok": false`` on the last line;
so does a platform that is not ``tpu``. On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed on earlier lines are host wall-clock of a whole phase
(compilation is reported separately, as set-up), not device metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "yi_6b"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the run's scale depends on. ``FULL`` is the chip run;
    the CPU rehearsal in the tests uses a smoke-sized instance."""

    n_layers: int | None = 16  # None keeps the config's depth
    smoke_model: bool = False  # the arch's smoke config instead of the real one
    slots: int = 8
    cache_len: int = 4096
    requests: int = 8
    prompt_lens: tuple[int, int] = (128, 512)  # inclusive range
    max_new: int = 32
    buckets: tuple[int, ...] = (256, 512)
    max_pack: int = 2
    horizon: int = 8


FULL = Sizes()

# flash attention vs its unchunked oracle (the kernel renormalizes per kv
# chunk, the oracle once): the test suite's tolerance
FLASH_RTOL, FLASH_ATOL = 5e-2, 5e-3
# sharded vs single-device first-step logits, max |diff| / max |logit|:
# bf16 weights, and tensor parallelism sums each row-parallel matmul from
# 4 bf16-rounded partials, once per layer
MESH_LOGIT_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def finish(ok: bool, device: dict | None, error: str | None = None) -> int:
    line = {"ok": bool(ok), "device": device}
    if error is not None:
        line["error"] = error
    print(json.dumps(line), flush=True)
    return 0 if ok else 1


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_line() -> str:
    import jax

    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use', 0) / 2**30:.3f}"
                     f"GiB peak={st.get('peak_bytes_in_use', 0) / 2**30:.3f}GiB")
    return "; ".join(parts)


def model_config(sizes: Sizes, numerics: str = "exact"):
    from repro.configs.base import get_config, get_smoke_config

    cfg = get_smoke_config(ARCH) if sizes.smoke_model else get_config(ARCH)
    if sizes.n_layers is not None:
        cfg = cfg.replace(n_layers=sizes.n_layers)
    return cfg.replace(numerics=numerics)


def fresh_library(table_dir: pathlib.Path):
    """Compile the default interpolation library in-process into an empty
    table cache (never a pre-existing one)."""
    from repro.api import Explorer, ExploreConfig

    shutil.rmtree(table_dir, ignore_errors=True)
    ex = Explorer(ExploreConfig(cache_dir=str(table_dir)))
    return ex, ex.compile()


def _cmp(got, want) -> tuple[bool, float]:
    import numpy as np

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return bool(np.array_equal(g, w)), float(np.max(np.abs(g - w)))


def check_kernels(ex, lib, cfg, sizes: Sizes, seed: int,
                  interpret: bool | None = None) -> list[str]:
    """The library kernels at decode shapes vs their jnp oracles. Returns
    one report line per check; raises CheckFailed on a mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flashattn.ops import attention_fused_library
    from repro.kernels.interp.kernel import rom_eval_2d
    from repro.kernels.interp.ops import library_walk
    from repro.kernels.interp.ref import library_walk_ref
    from repro.kernels.rmsnorm.ops import approx_rmsnorm_library
    from repro.kernels.rmsnorm.ref import fused_rmsnorm_lib_ref
    from repro.kernels.softmax.ops import approx_softmax_library, lib_meta
    from repro.kernels.softmax.ref import fused_softmax_lib_ref
    from repro.numerics.ops import table_eval_int

    rng = np.random.default_rng(seed)
    out = []
    r_max = lib.coeffs.shape[1]
    # every kind's in-kernel ROM read over its whole input domain
    for kind in lib.kinds:
        m = lib_meta(lib, kind)
        codes = np.arange(1 << m["in_bits"], dtype=np.int32)
        tiled = np.pad(codes, (0, (-codes.size) % 1024)).reshape(-1, 128)
        got = rom_eval_2d(jnp.asarray(tiled), lib.coeffs.reshape(-1, 3),
                          fid=m["fid"], r_max=r_max, **m["eval"],
                          interpret=interpret)
        got = np.asarray(got).reshape(-1)[:codes.size]
        want = np.asarray(table_eval_int(jnp.asarray(codes),
                                         ex.get_table(kind)))
        check(np.array_equal(got, want), f"ROM read {kind}: kernel != "
              f"table_eval_int at {int(np.sum(got != want))} codes")
    out.append(f"ROM read, {len(lib.kinds)} kinds x all codes: bitwise equal "
               f"to table_eval_int")
    # the fused multi-function walk, mixed function ids per element
    walk, dp = lib.walk_rows()
    n = sizes.slots * 1024
    fids = rng.integers(0, len(lib.kinds), n).astype(np.int32)
    codes = rng.integers(0, 1 << min(m.in_bits for m in lib.metas), n
                         ).astype(np.int32)
    got = library_walk(jnp.asarray(codes), jnp.asarray(fids), lib.coeffs,
                       walk, dp, use_kernel=True, interpret=interpret)
    want = library_walk_ref(jnp.asarray(codes), jnp.asarray(fids),
                            lib.coeffs, walk, dp)
    same, _ = _cmp(got, want)
    check(same, "library_walk: kernel != library_walk_ref")
    out.append(f"library_walk, {n} mixed elements: bitwise equal to "
               f"library_walk_ref")
    # rmsnorm over the decode batch at d_model
    d = cfg.d_model
    x = jnp.asarray(rng.normal(0, 2, (sizes.slots, d)), jnp.bfloat16)
    gamma = jnp.asarray(rng.normal(1, 0.1, d), jnp.float32)
    got = approx_rmsnorm_library(x, gamma, lib, use_kernel=True,
                                 interpret=interpret)
    want = fused_rmsnorm_lib_ref(x, gamma, lib.coeffs, lib_meta(lib, "rsqrt"))
    same, diff = _cmp(got, want)
    check(same, f"rmsnorm: kernel != oracle, max|diff| {diff!r}")
    out.append(f"rmsnorm ({sizes.slots}, {d}) bf16: bitwise equal to "
               f"fused_rmsnorm_lib_ref")
    # softmax over decode-shaped score rows
    s = jnp.asarray(rng.normal(0, 3, (sizes.slots * cfg.n_heads,
                                      sizes.cache_len)), jnp.float32)
    got = approx_softmax_library(s, lib, use_kernel=True, interpret=interpret)
    want = fused_softmax_lib_ref(s, lib.coeffs, lib_meta(lib, "exp2neg"),
                                 lib_meta(lib, "recip"))
    same, diff = _cmp(got, want)
    check(same, f"softmax: kernel != oracle, max|diff| {diff!r}")
    out.append(f"softmax {tuple(s.shape)} f32: bitwise equal to "
               f"fused_softmax_lib_ref")
    # flash decode against a partially filled KV pool
    b, h, kvh, hd = sizes.slots, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, sizes.cache_len, kvh, hd), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, sizes.cache_len, kvh, hd), jnp.bfloat16)
    fill = np.linspace(sizes.prompt_lens[0], sizes.prompt_lens[1] +
                       sizes.max_new, b).astype(np.int32)
    fill = np.minimum(fill, sizes.cache_len)
    kv_pos = np.full((b, sizes.cache_len), -1, np.int32)
    for i, f in enumerate(fill):
        kv_pos[i, :f] = np.arange(f)
    kw = dict(causal=True, q_pos=jnp.asarray(fill - 1)[:, None],
              kv_pos=jnp.asarray(kv_pos))
    got = attention_fused_library(q, kc, vc, lib, use_kernel=True,
                                  interpret=interpret, **kw)
    want = attention_fused_library(q, kc, vc, lib, use_kernel=False, **kw)
    same, diff = _cmp(got, want)
    g32, w32 = (np.asarray(a, np.float32) for a in (got, want))
    ok = bool(np.all(np.abs(g32 - w32) <= FLASH_ATOL + FLASH_RTOL * np.abs(w32)))
    check(ok, f"flash decode: max|diff| {diff!r}")
    out.append(f"flash decode q{tuple(q.shape)} kv{tuple(kc.shape)} bf16 "
               f"(GQA group {h // kvh}): bitwise={same} max|diff|={diff!r} "
               f"(rtol {FLASH_RTOL}, atol {FLASH_ATOL}; the oracle is "
               f"unchunked)")
    return out


def make_prompts(cfg, sizes: Sizes, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = sizes.prompt_lens
    return [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
            for n in rng.integers(lo, hi + 1, sizes.requests)]


def build_engine(cfg, params, lib, sizes: Sizes, mesh=None):
    from repro.serve.engine import ServeEngine

    return ServeEngine(cfg, params, slots=sizes.slots,
                       cache_len=sizes.cache_len,
                       library=lib if cfg.numerics != "exact" else None,
                       aot_buckets=sizes.buckets, max_pack=sizes.max_pack,
                       horizon=sizes.horizon, mesh=mesh)


def engine_health(eng) -> None:
    """Zero degradations, faults, failed requests and fused-attention
    refusals — a run that fell back anywhere is not a pass."""
    d = eng.stats["degradations"]
    check(not (sum(d.values()) if isinstance(d, dict) else d),
          f"degradations: {d}")
    check(not eng.faults, f"faults: {eng.faults}")
    check(not eng.failed,
          f"failed requests: {[(r.rid, r.error) for r in eng.failed]}")
    check(eng.stats["attn_glue_fallbacks"] == 0,
          f"fused attention fell back {eng.stats['attn_glue_fallbacks']}x")


def serve(eng, prompts, sizes: Sizes, vocab: int) -> tuple[dict, float]:
    """Submit every prompt, run to completion; returns ({rid: tokens},
    wall seconds)."""
    from repro.serve.engine import Request

    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, sizes.max_new))
    done = eng.run()
    wall = time.perf_counter() - t0
    engine_health(eng)
    check(len(done) == len(prompts),
          f"{len(done)} of {len(prompts)} requests finished")
    toks = {r.rid: list(r.out) for r in done}
    for rid, t in toks.items():
        check(len(t) == sizes.max_new, f"request {rid}: {len(t)} tokens")
        check(all(0 <= x < vocab for x in t),
              f"request {rid}: token outside [0, {vocab})")
    check(eng.stats["aot_misses"] == 0,
          f"AOT misses: {eng.stats['aot_misses']}")
    return toks, wall


def fused_tick_kernels(eng, sizes: Sizes) -> tuple[int, list[str]]:
    """The warmed fused tick: how many Mosaic kernel calls its compiled
    HLO holds, and which kernels its lowering names."""
    import re

    from repro.serve import aot as aot_mod

    exe = aot_mod.lookup(eng._aot_key("tick", sizes.horizon))
    check(exe is not None, "no AOT-compiled fused tick")
    n_calls = exe.as_text().count("tpu_custom_call")
    with eng._ctx():
        lowered = eng._tick_jit(sizes.horizon).lower(
            eng.params, eng._tok_dev, eng._pos_dev, eng._live_dev,
            eng.caches, library=eng.library).as_text()
    names = sorted(set(re.findall(r'kernel_name = "([A-Za-z0-9_]+)"',
                                  lowered)))
    return n_calls, names


def phase_serve(cfg_base, params, lib, sizes: Sizes, seed: int,
                expect_kernels: bool) -> dict:
    """Serve the prompts under exact and interp-fused numerics."""
    prompts = make_prompts(cfg_base, sizes, seed)
    lens = [len(p) for p in prompts]
    log(f"requests: {len(prompts)}, prompt lengths {lens}, "
        f"{sizes.max_new} new tokens each, slots {sizes.slots}, "
        f"cache {sizes.cache_len}, buckets {sizes.buckets}")
    results = {}
    for numerics in ("exact", "interp-fused"):
        cfg = cfg_base.replace(numerics=numerics)
        t0 = time.perf_counter()
        eng = build_engine(cfg, params, lib, sizes)
        setup = time.perf_counter() - t0
        log(f"[{numerics}] set-up (engine + AOT compile of "
            f"{eng.stats['aot_compiles']} programs): {setup:.3f}s")
        toks, wall = serve(eng, prompts, sizes, cfg.vocab_size)
        n_tok = sum(len(t) for t in toks.values())
        log(f"[{numerics}] served {len(toks)} requests, {n_tok} tokens, "
            f"{eng.stats['ticks']} ticks, {eng.stats['admit_dispatches']} "
            f"admissions, wall {wall:.3f}s (host clock, warmed); "
            f"degradations 0, faults 0, failed 0")
        if numerics == "interp-fused":
            n_calls, names = fused_tick_kernels(eng, sizes)
            log(f"[{numerics}] fused tick: {n_calls} tpu_custom_call, "
                f"kernels {names}")
            if expect_kernels:
                check(n_calls > 0, "fused tick holds no tpu_custom_call")
                for k in ("_flash_lib_kernel", "_rmsnorm_lib_kernel"):
                    check(k in names, f"fused tick lacks {k}")
        log(f"memory: {memory_line()}")
        results[numerics] = toks
        del eng
        gc.collect()
    a, b = results["exact"], results["interp-fused"]
    same = sum(x == y for rid in a for x, y in zip(a[rid], b[rid]))
    log(f"exact vs interp-fused greedy tokens agree at {same} of "
        f"{sum(len(t) for t in a.values())} positions (information only: "
        f"the table numerics are approximate)")
    return results


def phase_mesh(cfg, params, lib, sizes: Sizes, seed: int,
               tp: int = 4) -> dict:
    """The same config on a (1, tp) TP serve mesh vs one device: per-device
    memory, first-step logits agreement, served tokens."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_serve_mesh

    prompts = make_prompts(cfg, sizes, seed)
    mesh = make_serve_mesh(1, tp)
    logits, toks = {}, {}
    for name, m in (("single", None), (f"mesh1x{tp}", mesh)):
        t0 = time.perf_counter()
        eng = build_engine(cfg, params, lib, sizes, mesh=m)
        log(f"[{name}] set-up (placement + AOT compile of "
            f"{eng.stats['aot_compiles']} programs): "
            f"{time.perf_counter() - t0:.3f}s")
        if m is not None:
            wq = next(v for k, v in _flat(eng.params) if k.endswith("wq"))
            log(f"[{name}] a wq leaf: global {tuple(wq.shape)}, per-device "
                f"shard {tuple(wq.sharding.shard_shape(wq.shape))}")
        log(f"[{name}] memory after placement: {memory_line()}")
        with eng._ctx():
            lg, _, _ = eng._prefill_fnum(eng.params,
                                         jnp.asarray(prompts[0])[None, :],
                                         library=eng.library)
        logits[name] = np.asarray(lg, np.float32)
        toks[name], wall = serve(eng, prompts, sizes, cfg.vocab_size)
        log(f"[{name}] served {len(toks[name])} requests, wall {wall:.3f}s "
            f"(host clock)")
        del eng
        gc.collect()
    a, b = logits["single"], logits[f"mesh1x{tp}"]
    check(np.all(np.isfinite(b)), "sharded logits not finite")
    rel = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    agree = bool(np.argmax(a[0, -1]) == np.argmax(b[0, -1]))
    log(f"first-step logits, sharded vs single: max|diff|/max|logit| = "
        f"{rel!r} (limit {MESH_LOGIT_RTOL}); greedy first token equal: "
        f"{agree}")
    check(rel <= MESH_LOGIT_RTOL, f"sharded logits differ: {rel!r}")
    ta, tb = toks["single"], toks[f"mesh1x{tp}"]
    same = sum(x == y for rid in ta for x, y in zip(ta[rid], tb[rid]))
    log(f"served tokens equal at {same} of "
        f"{sum(len(t) for t in ta.values())} positions")
    return {"rel": rel, "tokens_equal": same}


def _flat(tree):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(getattr(p, "key", p)) for p in path), leaf)
            for path, leaf in flat]


def run(chips: int, seed: int, sizes: Sizes = FULL,
        interpret: bool | None = None,
        table_dir: pathlib.Path = ROOT / "artifacts" / "chip_smoke") -> None:
    """Every phase of the chosen run; raises on the first failed check.
    ``table_dir`` is emptied and receives the freshly generated tables."""
    import jax

    from repro.models import transformer as tf

    cfg = model_config(sizes)
    log(f"config: {ARCH} d_model {cfg.d_model}, {cfg.n_heads} heads, "
        f"{cfg.n_kv_heads} KV heads, head_dim {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.param_dtype}; depth cut "
        f"to {cfg.n_layers} layers (published: 32); random weights, seed "
        f"{seed}")
    t0 = time.perf_counter()
    ex, lib = fresh_library(table_dir)
    log(f"library: {list(lib.kinds)} compiled into a fresh table cache, ROM "
        f"{tuple(lib.coeffs.shape)}, {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    params = tf.init_params(jax.random.key(seed), cfg)
    jax.block_until_ready(params)
    n_par = sum(x.size for x in jax.tree.leaves(params))
    log(f"weights: {n_par} parameters "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.3f} GiB), "
        f"init {time.perf_counter() - t0:.3f}s; memory: {memory_line()}")
    if chips > 1:
        phase_mesh(cfg.replace(numerics="interp-fused"), params, lib, sizes,
                   seed, tp=chips)
        return
    t0 = time.perf_counter()
    for line in check_kernels(ex, lib, cfg, sizes, seed, interpret):
        log(f"kernel check: {line}")
    log(f"kernel checks: {time.perf_counter() - t0:.3f}s incl. compile")
    phase_serve(cfg, params, lib, sizes, seed,
                expect_kernels=jax.default_backend() == "tpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"chip_smoke: no repro sources under {ROOT / 'src'}")
        return finish(False, None, "repository sources not found")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import setup_compile_cache

    log(f"compile cache: {setup_compile_cache()}")
    dev = device_info()
    log(f"device: {dev}")
    if dev["platform"] != "tpu":
        return finish(False, dev, f"JAX platform is {dev['platform']!r}, "
                                  f"not 'tpu': nothing was run")
    if dev["count"] < args.chips:
        return finish(False, dev, f"{args.chips} chips asked, "
                                  f"{dev['count']} present")
    try:
        run(args.chips, args.seed)
    except Exception as e:  # noqa: BLE001 - every failure ends as ok: false
        traceback.print_exc()
        return finish(False, dev, f"{type(e).__name__}: {e}")
    return finish(True, dev)


if __name__ == "__main__":
    sys.exit(main())
