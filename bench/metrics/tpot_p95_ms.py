"""95th percentile, over the requests completed in the window, of (last
token - first token) / (output tokens - 1): the mean gap between a request's
output tokens (tokens arrive in chunks of up to the engine's horizon)."""
from bench import stats


def read(run):
    v = stats.p95(stats.tpot_s(run.recs, run.seconds))
    return None if v is None else 1e3 * v
