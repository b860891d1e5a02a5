"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the XLA op intervals / window), averaged over the cell's
chips. Percent."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    devs = run.trace["devices"][:run.cell.chips]
    busy = sum(trace.busy_ns(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
