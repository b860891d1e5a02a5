"""95th percentile of time to first token over every request due in the
window, from its scheduled arrival; a request still without a first token at
the window's end enters as (window end - arrival)."""
from bench import stats


def read(run):
    v = stats.p95(stats.ttft_s(run.recs, run.seconds))
    return None if v is None else 1e3 * v
