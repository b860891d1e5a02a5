"""Kernels: the fused flash-attention kernel's share of its roofline in the
traced window: the least time of its calls (``bench/counts/flash_lib``) over
its device time in the trace. Percent.

The kernel is found by its operands (its name is not in the trace): a
Pallas call of six operands, queries, keys and values, query positions as
an (N, Sq, 1) int32 column, key positions, and the ROM."""
from bench import trace
from bench.counts import flash_lib


def is_flash(_name, operands):
    if operands is None or len(operands) != 6:
        return False
    (t3, s3), (t4, s4), (t5, _s5) = operands[3], operands[4], operands[5]
    return (t3 == t4 == t5 == "s32" and len(s3) == 3 and s3[-1] == 1
            and len(s4) == 3 and all(len(o[1]) == 3 for o in operands[:3]))


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    t = trace.op_time_ns(run.trace["devices"][0], is_flash, lo, hi) / 1e9
    if t <= 0:
        return None
    least = sum(flash_lib.least_s(run.counts, run.hf, s, run.peaks)
                for s in run.traced_steps())
    return 100.0 * least / t
