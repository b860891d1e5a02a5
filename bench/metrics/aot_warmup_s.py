"""Engine set-up: seconds of the served engine's ``engine.aot`` span (the
AOT warm-up inside the newest ``engine.init`` of the process): lowering
and compiling, or loading from JAX's persistent cache, every tick and
admission program."""
from bench import engine_spans


def read(run):
    aot = engine_spans.aot_span()
    return None if aot is None else aot.dur
