"""Engine decode tick: 95th percentile, over the requests due in the
window, of ``first_token_returned_at - first_token_at``: how long a first
token waits on the host for the engine step that made it to return (the
tick that follows admission in the same step)."""
from bench import engine_spans


def read(run):
    return engine_spans.p95_ms(run, "first_token_at",
                               "first_token_returned_at")
