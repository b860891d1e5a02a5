"""Device / engine host: share of the traced window in which the device is
idle while the host is inside ``engine.step`` but not inside
``engine.sync`` (admission prep, token hand-out, retirement, checks),
averaged over the cell's chips. Read from the host plane of the run's
``.xplane.pb`` (``artifacts/bench_trace``), on the profiler's clock.
Percent."""
from bench import engine_spans
from bench import trace


def read(run):
    if run.trace is None:
        return None
    tdir = run.cell.bench_dir.parent / "artifacts" / "bench_trace"
    try:
        host = engine_spans.host_spans(trace.find_xplane(str(tdir)))
    except RuntimeError:
        return None
    if not host[engine_spans.STEP]:
        return None
    lo, hi = trace.window(run.trace)
    devs = run.trace["devices"][:run.cell.chips]
    idle = sum(engine_spans.host_idle_ns(d, host, lo, hi) for d in devs)
    return 100.0 * idle / len(devs) / (hi - lo)
