#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json``, one seed, one process.

    python3 bench/run.py --workload yi_6b.interp.chat --seed 7 \\
        --seconds 30 --trace 0

Runs on the accelerator it is started on and never falls back to the CPU:
on another platform, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. JAX's persistent compilation cache is kept
at ``artifacts/jax_cache`` inside the checkout, so only the first run of a
cell compiles.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace
1`` its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``compared``: each number the correctness check compared, with its
limit. The same numbers end standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", type=pathlib.Path, default=None,
                    help="write the open loop's steps and requests (JSON)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is missing ({ROOT / 'src'}); "
              f"nothing was run", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts"
                                                  / "jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import load_cell, run

    cell = load_cell(args.workload, ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s); nothing "
              f"was run", file=sys.stderr)
        return 1
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), T_START, ROOT, args.record)
    except Exception:  # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        return 1
    for k, c in result["compared"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
