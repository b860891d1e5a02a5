"""Serving launcher CLI: continuous-batching greedy decoding demo.

    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --smoke \
        --requests 8 --slots 4 --prompt-len 16 --max-new 12

With ``--numerics interp`` the engine serves from a compiled interpolation
library; ``--library PATH`` loads a saved artifact (no exploration at all),
``--save-library PATH`` persists the compiled artifact for the next launch.

Per-layer heterogeneous numerics (DESIGN.md §16): ``--plan PATH`` serves
under a saved :class:`repro.plan.NumericsPlan` (the schema-versioned
snapshot envelope ``repro.launch.dse plan --save-plan`` emits — one
backend + library slot per layer x op site); ``--save-plan PATH`` writes
the plan the engine actually served under (useful with ``--numerics`` to
snapshot a uniform plan for later editing).

Robustness knobs (DESIGN.md §14): ``--deadline-ms N`` gives every request a
TTL (expired work is retired with a structured ``deadline_exceeded`` error),
``--max-queue N`` bounds the admission queue (overflow submissions raise
``Rejected(reason="queue_full")`` instead of growing memory), ``--journal
PATH`` records admissions and emitted tokens through an fsync'd append-only
journal, and ``--resume`` (with ``--journal``) rebuilds the engine from that
journal after a crash — completed requests are not re-served and in-flight
streams continue bitwise where they left off.

The exit status is 0 only for a clean run: any degradation-ladder step,
recorded fault or failed request exits 1 (after the summary), so a run that
quietly fell back from the fused datapath cannot pass for a healthy one.
The persistent compilation cache is placed by
:func:`repro.launch.compile_cache.setup_compile_cache`.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.api import InterpLibrary
from repro.configs.base import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.models import transformer as tf
from repro.serve import Rejected, ServeEngine
from repro.serve.engine import Request


def degraded(eng) -> list[str]:
    """Why a finished engine run is not clean (empty = clean): degradation
    ladder steps, recorded faults, failed requests."""
    why = []
    d = eng.stats["degradations"]
    if (sum(d.values()) if isinstance(d, dict) else d):
        why.append(f"degradations={d}")
    if eng.faults:
        why.append(f"faults={len(eng.faults)}")
    if eng.failed:
        why.append(f"failed={len(eng.failed)}")
    return why


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--numerics", choices=["exact", "interp"], default=None)
    ap.add_argument("--library", default=None,
                    help="serve from this saved InterpLibrary (json/npz base)")
    ap.add_argument("--save-library", default=None,
                    help="persist the engine's compiled library here")
    ap.add_argument("--plan", default=None,
                    help="serve under this saved NumericsPlan snapshot "
                         "(per-layer x per-op-site numerics)")
    ap.add_argument("--save-plan", default=None,
                    help="write the served plan (from --plan, or a uniform "
                         "plan matching --numerics) as a snapshot")
    ap.add_argument("--serial", action="store_true",
                    help="per-op dispatch path (the pre-fused oracle) "
                         "instead of the fused single-dispatch tick")
    ap.add_argument("--horizon", type=int, default=8,
                    help="fused tick: max decode steps per dispatch")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request TTL; expired requests are retired "
                         "with a structured deadline_exceeded error")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission queue bound; overflow submissions are "
                         "rejected (reason=queue_full), never buffered")
    ap.add_argument("--journal", default=None,
                    help="fsync'd serve journal (admissions + tokens); "
                         "makes the run crash-recoverable via --resume")
    ap.add_argument("--resume", action="store_true",
                    help="rebuild engine state from --journal instead of "
                         "submitting fresh requests")
    ap.add_argument("--mesh", default=None, metavar="DxT",
                    help="serve on a (data, tp) device mesh, e.g. 2x4 "
                         "(bare N means Nx1); KV pool batch-sharded over "
                         "data, heads over tp, ROM replicated (DESIGN.md "
                         "§17). Needs data*tp <= len(jax.devices())")
    ap.add_argument("--aot-buckets", default=None, metavar="B1,B2,...",
                    help="AOT warm-up: compile the decode tick and a packed "
                         "prefill program per bucket at construction; "
                         "'default' uses the built-in table clipped to "
                         "--cache-len")
    ap.add_argument("--max-pack", type=int, default=4,
                    help="max prompts packed into one bucketed prefill "
                         "dispatch (power-of-two group sizes)")
    ap.add_argument("--async-host", action="store_true",
                    help="detokenize/journal on a background host thread "
                         "behind a bounded queue (DESIGN.md §17)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.resume and not args.journal:
        ap.error("--resume requires --journal")
    setup_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.numerics:
        cfg = cfg.replace(numerics=args.numerics)
    if args.plan:
        from repro.plan import load_plan

        plan = load_plan(args.plan)
        if plan.n_layers != cfg.n_layers:
            ap.error(f"--plan has {plan.n_layers} layers but {args.arch} "
                     f"(smoke={args.smoke}) has {cfg.n_layers}")
        cfg = cfg.replace(plan=plan)
        if args.library:
            ap.error("--plan engines compile one library per plan slot; "
                     "--library cannot override them")
    if args.library or args.save_library:
        if args.numerics == "exact":
            ap.error("--library/--save-library require interp numerics")
        if cfg.plan is None and cfg.numerics != "interp":
            cfg = cfg.replace(numerics="interp")  # the flags imply it
    if args.save_plan:
        from repro.plan import plan_for, save_plan

        served = cfg.plan if cfg.plan is not None else plan_for(cfg)
        save_plan(args.save_plan, served, seed=args.seed,
                  meta_extra={"arch": args.arch, "smoke": args.smoke})
        print(f"saved plan -> {args.save_plan}")
    library = InterpLibrary.load(args.library) if args.library else None
    params = tf.init_params(jax.random.key(args.seed), cfg)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh, parse_mesh_spec

        data, tp = parse_mesh_spec(args.mesh)
        mesh = make_serve_mesh(data, tp)
        print(f"serve mesh: data={data} x tp={tp} "
              f"({len(jax.devices())} devices visible)")
    buckets = None
    if args.aot_buckets:
        buckets = (True if args.aot_buckets == "default" else
                   tuple(int(b) for b in args.aot_buckets.split(",")))
    kw = dict(slots=args.slots, cache_len=args.cache_len, library=library,
              fused=not args.serial, horizon=args.horizon,
              max_queue=args.max_queue,
              deadline_s=(args.deadline_ms / 1e3
                          if args.deadline_ms is not None else None),
              mesh=mesh, aot_buckets=buckets, max_pack=args.max_pack,
              async_host=args.async_host)
    t0 = time.perf_counter()
    if args.resume:
        eng = ServeEngine.resume(args.journal, cfg, params, **kw)
    else:
        eng = ServeEngine(cfg, params, journal=args.journal, **kw)
    if args.save_library and eng.library is not None:
        if isinstance(eng.library, dict):  # plan engine: one artifact/slot
            for key, lib in sorted(eng.library.items()):
                print(f"saved library [{key}] -> "
                      f"{lib.save(f'{args.save_library}.{key}')}")
        else:
            print(f"saved library -> {eng.library.save(args.save_library)}")
    if not args.resume:
        rng = np.random.default_rng(args.seed)
        for i in range(args.requests):
            try:
                eng.submit(Request(i, rng.integers(
                    0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                    args.max_new))
            except Rejected as e:
                print(f"  req {i} rejected ({e.reason})")
    done = eng.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s incl. compile; "
          f"{eng.stats['dispatches']} dispatches / "
          f"{eng.stats['decode_steps']} decode steps)")
    if args.resume:
        print(f"  resumed: {eng.stats['resumed']} in-flight replayed "
              f"({eng.stats['resume_replay_steps']} teacher-forced steps), "
              f"{eng.stats['resume_skipped_done']} already-done skipped")
    if eng.failed:
        print(f"  failed: {len(eng.failed)} "
              f"({sorted({r.error for r in eng.failed})})")
    if eng.faults:
        print(f"  faults: {eng.faults}")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    why = degraded(eng)
    if why:
        print(f"  NOT CLEAN: {', '.join(why)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
