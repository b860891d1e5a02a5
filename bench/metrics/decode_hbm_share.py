"""Model step: HBM bytes the decode ticks of the traced window must move
(``bench/counts/step.decode_bytes``), over the device time of the tick
programs (``jit_tick`` runs in the trace) times the HBM bandwidth. Percent."""
from bench import trace
from bench.counts import step

PROGRAM = "jit_tick"


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    t = trace.module_time_ns(run.trace["devices"][0], PROGRAM, lo, hi) / 1e9
    if t <= 0:
        return None
    b = sum(step.decode_bytes(run.counts, run.hf, s)
            for s in run.traced_steps())
    return 100.0 * b / (t * run.peaks["hbm_bytes_per_s"])
