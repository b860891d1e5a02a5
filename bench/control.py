#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's and the
control's widest logit gap, seed by seed, in one process.

    python3 bench/control.py --workload yi_6b.interp.chat --seconds 20 \\
        --seeds 11,12,13

Per seed: the cell's weights, a fresh engine (compiled programs come from
the in-process cache), the warm-up, a window of the cell's own traffic at
its rate, then on the same sample of finished requests as a run draws: the
program's ``max_logit_gap`` (its lower reading) and the control's -- the
reference computed in fp8 put in the program's place, reading at each
position the gap of the token the fp8 forward puts first (its upper
reading). Prints one JSON line per seed. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts"
                                                  / "jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import correctness, harness, traffic

    cell = harness.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 1
    vocab = cell.hf["vocab_size"]
    for seed in (int(s) for s in args.seeds.split(",")):
        params = harness.make_weights(cell, seed)
        eng = harness.build_engine(cell, params,
                                   ROOT / "artifacts" / "bench_tables")
        harness.warm_up(eng, cell, vocab, seed)
        sched = traffic.arrivals(cell.mix, cell.params["rate"], args.seconds,
                                 seed, vocab)
        recs, _, _, tainted = harness.drive(eng, sched, args.seconds)
        finished = [r for r in recs if r.done_t is not None and not r.failed]
        del eng, params
        gc.collect()
        prog = correctness.check(cell, finished, seed)["max_logit_gap"]
        ctrl = correctness.check(cell, finished, seed,
                                 quant="fp8")["max_logit_gap"]
        print(json.dumps({"seed": seed, "finished": len(finished),
                          "fault": tainted is not None,
                          "program": prog["value"], "control": ctrl["value"],
                          "limit": prog["limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
