#!/usr/bin/env python3
"""Knee search for a cell's offered rate: the highest rate the engine
sustains, whose queue does not grow over the window.

    python3 bench/sweep.py --workload yi_6b.interp.chat --seed 5 \\
        --seconds 51 --rates 0.3,0.4,0.5,0.6,0.7

One process on the chip: the weights, library and compiled programs are
built once; each rate gets a fresh engine (programs from the in-process
cache), the cell's mix at that rate with its lead-in (``lead_s``, long
enough for the slots to fill at a rate near the knee, so the window opens
on a loaded engine), and a window of ``--seconds``. The queue (requests
that have arrived and hold no slot) and the occupancy (requests holding a
slot) are sampled at eleven points from the window's start to its end.
A rate is not sustained when its queue grows (the mean over the window's
last third exceeds that over its first third by more than two requests) or
stands (the queue never falls under two in the window's second half: the
slots are full throughout). The knee is the highest rate below the first
one not sustained. Each line also gives the output tokens offered (the
rate times the mean output length of the mix) beside those delivered in
the window. Prints one line per rate and the knee; the cell's ``rate`` is
set to 0.8 x the knee by hand.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def queued(recs, t: float) -> int:
    return sum(1 for r in recs if r.due <= t
               and (r.admit_t is None or r.admit_t > t))


def holding(recs, t: float) -> int:
    return sum(1 for r in recs if r.admit_t is not None and r.admit_t <= t
               and (r.done_t is None or r.done_t > t))


def sustained(queue: list) -> bool:
    third, half = len(queue) // 3, len(queue) // 2
    first = sum(queue[:third]) / third
    last = sum(queue[-third:]) / third
    return last <= first + 2 and min(queue[half:]) < 2


def measure(eng, cell, rate: float, seconds: float, seed: int) -> dict:
    """One rate on a warmed engine: the row that ``main`` prints."""
    import numpy as np

    from bench import harness, stats, traffic

    vocab = cell.hf["vocab_size"]
    sched = traffic.arrivals(cell.mix, rate, seconds, seed, vocab)
    t0 = time.perf_counter()
    recs, steps, _, tainted = harness.drive(eng, sched, seconds)
    s = seconds
    pts = [s * k / 10 for k in range(11)]
    queue = [queued(recs, t) for t in pts]
    mean_out = float(np.mean(traffic.lengths(cell.mix["output_tokens"],
                                             1000)))
    offered = rate * mean_out
    delivered = harness.module(
        "metrics", "output_tokens_per_s", cell.bench_dir).read(
        harness.Run(cell, s, recs, steps, 0.0, {}, None))
    return {"rate": rate, "queue": queue,
            "slots_held": [holding(recs, t) for t in pts],
            "sustained": sustained(queue) and tainted is None,
            "offered_tokens_per_s": offered,
            "output_tokens_per_s": delivered,
            "queue_wait_p95_ms": 1e3 * stats.p95(stats.queue_wait_s(recs, s)),
            "ttft_p95_ms": 1e3 * stats.p95(stats.ttft_s(recs, s)),
            "due": sum(1 for r in recs if 0 <= r.due < s),
            "completed": sum(1 for r in recs if r.done_t is not None
                             and 0 <= r.done_t <= s),
            "fault": tainted is not None,
            "wall_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts"
                                                  / "jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 1
    params = harness.make_weights(cell, args.seed)
    knee, stop = None, False
    for rate in (float(r) for r in args.rates.split(",")):
        eng = harness.build_engine(cell, params,
                                   ROOT / "artifacts" / "bench_tables")
        harness.warm_up(eng, cell, cell.hf["vocab_size"], args.seed)
        row = measure(eng, cell, rate, args.seconds, args.seed)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            stop = True
        elif not stop:
            knee = rate
        del eng
        gc.collect()
    print(json.dumps({"workload": cell.name, "knee": knee,
                      "rate_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
