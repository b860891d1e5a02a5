"""Kernels: the library activation kernel's (the MLP's SiLU walk) share of
its roofline in the traced window: bytes in and out over the HBM bandwidth
(``bench/counts/act_lib``) over its device time in the trace. Percent.
The kernel is the program's ``_library_eval*`` Pallas call."""
from bench import trace
from bench.counts import act_lib


def is_act(name, operands):
    return operands is not None and name.startswith("_library_eval")


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    t = trace.op_time_ns(run.trace["devices"][0], is_act, lo, hi) / 1e9
    if t <= 0:
        return None
    least = sum(act_lib.least_s(run.counts, run.hf, s, run.peaks)
                for s in run.traced_steps())
    return 100.0 * least / t
