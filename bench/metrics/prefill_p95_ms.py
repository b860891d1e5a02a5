"""Engine admission: 95th percentile, over the requests due in the window,
of the engine's own stamps ``first_token_at - admitted_at``: from just
before the prefill dispatch that admits a request to its first token on
the host. A request not yet admitted by the run's end does not enter."""
from bench import engine_spans


def read(run):
    return engine_spans.p95_ms(run, "admitted_at", "first_token_at")
