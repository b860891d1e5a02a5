"""Benchmark suite entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only a,b,c]

| module            | paper artifact                         |
|-------------------|----------------------------------------|
| table1            | Table I (proposed cols, runtime, LUB)  |
| table2            | Table II (LUT widths vs Remez)         |
| claim21           | SII-A Claim II.1 speedup + engines     |
| scaling           | SII-A O(R^-3) + exponential-in-bits    |
| batched_engine    | batched vs pooled generation, min-R    |
| fleet_compile     | fleet vs serial manifest compile/min-R |
| fig3_lub_sweep    | Figs 2-3 area-delay vs LUT height      |
| kernels_bench     | TPU adaptation: kernels + table accuracy |
| serve_path        | fused-library vs per-table decode numerics |
| decode_fused      | fused serve tick vs serial decode path |
| roofline_report   | SRoofline table from the dry-run sweep |
| segment_rom       | non-uniform (ROM v2) vs uniform layout |
| plan_serve        | per-layer NumericsPlan serving + auto-assigner |
| serve_sharded     | mesh-sharded + AOT-warmed serving tier |

After a run that produced them, the claim21 + batched_engine rows are
folded into ``artifacts/bench/BENCH_2.json``, the serve_path rows into
``BENCH_3.json``, the fleet_compile rows into ``BENCH_4.json``, and the
decode_fused rows into ``BENCH_5.json``, the segment_rom rows into
``BENCH_8.json``, the plan_serve rows into ``BENCH_9.json``, and the
serve_sharded rows into ``BENCH_10.json`` — the per-PR perf snapshots
tracked by the CI bench-smoke, segment-smoke, plan-smoke and shard-smoke
jobs. (``BENCH_6.json`` is written by the DSE study CLI,
``repro.launch.dse --emit-bench``, not by this runner.)

Snapshots go through ``repro.dse.record.update_snapshot``: every file is
schema-versioned and stamped with the seed, jax version and device
platform it was produced under, and a pre-existing unversioned snapshot
is backed up (``*.pre-schema.json``) instead of silently overwritten.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "bench"

BENCH_SEED = 0  # every benchmark module keys its PRNGs off seed 0
QUICK_RUN = False  # set by main(); stamped into snapshot meta

# snapshot file -> {module -> tables folded into it}
_SNAPSHOTS = {
    "BENCH_2.json": {
        "claim21": ("claim21_search", "claim21_endtoend"),
        "batched_engine": ("batched_vs_pooled", "min_regions_search"),
    },
    "BENCH_3.json": {
        "serve_path": ("serve_path_decode", "serve_path_ensemble"),
    },
    "BENCH_4.json": {
        "fleet_compile": ("fleet_compile", "fleet_min_regions"),
    },
    "BENCH_5.json": {
        "decode_fused": ("decode_fused",),
    },
    "BENCH_7.json": {
        "chaos_serve": ("chaos_overhead", "chaos_faults", "chaos_recovery"),
    },
    "BENCH_8.json": {
        "segment_rom": ("segment_rom", "segment_serve"),
    },
    "BENCH_9.json": {
        "plan_serve": ("plan_bitwise", "plan_auto"),
    },
    "BENCH_10.json": {
        "serve_sharded": ("serve_sharded_offline", "serve_sharded_online"),
    },
}


def _emit_snapshots(ran: set) -> None:
    # refresh only the tables whose module ran THIS invocation (stale
    # per-table JSONs from an earlier run must not be stamped into the
    # snapshot), but keep the other modules' existing tables — a partial
    # --only run must not truncate the tracked snapshots
    from repro.dse.record import read_snapshot, update_snapshot

    for snap, sources in _SNAPSHOTS.items():
        snap_path = ART / snap
        fresh = {}
        for mod, tables in sources.items():
            if mod not in ran:
                continue
            for name in tables:
                path = ART / f"{name}.json"
                if path.exists():
                    # per-table files are themselves versioned envelopes
                    # (benchmarks.common.emit); legacy bare lists unwrap too
                    fresh[name] = read_snapshot(path).get(name)
        if fresh:
            update_snapshot(snap_path, fresh, seed=BENCH_SEED,
                            meta_extra={"quick": QUICK_RUN})
            print(f"\nwrote {snap_path} (refreshed {sorted(fresh)})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced precisions (CI-speed run)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args()
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    global QUICK_RUN
    QUICK_RUN = args.quick
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    from benchmarks import (batched_engine, chaos_serve, claim21,
                            decode_fused, fig3_lub_sweep, fleet_compile,
                            kernels_bench, plan_serve, roofline_report,
                            scaling, segment_rom, serve_path, serve_sharded,
                            table1, table2)
    mods = {
        "table1": table1, "table2": table2, "claim21": claim21,
        "scaling": scaling, "batched_engine": batched_engine,
        "fleet_compile": fleet_compile,
        "fig3_lub_sweep": fig3_lub_sweep, "kernels_bench": kernels_bench,
        "serve_path": serve_path, "decode_fused": decode_fused,
        "chaos_serve": chaos_serve, "roofline_report": roofline_report,
        "segment_rom": segment_rom, "plan_serve": plan_serve,
        "serve_sharded": serve_sharded,
    }
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= set(mods):
        sys.exit(f"unknown --only module(s): {sorted(only - set(mods))}")
    failures = []
    ran = set()
    for name, mod in mods.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        print(f"\n=== {name} ===", flush=True)
        try:
            mod.run()
            ran.add(name)
            print(f"--- {name}: {time.perf_counter()-t0:.1f}s", flush=True)
        except Exception as e:
            failures.append((name, repr(e)))
            print(f"--- {name} FAILED: {e!r}", flush=True)
    _emit_snapshots(ran)
    if failures:
        print(f"\n{len(failures)} benchmark(s) failed: {failures}")
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
