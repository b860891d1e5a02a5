"""Engine admission: 95th percentile of due time to the start of the engine
step that admitted the request, over every request due in the window (one
still queued at the window's end enters as window end - arrival)."""
from bench import stats


def read(run):
    v = stats.p95(stats.queue_wait_s(run.recs, run.seconds))
    return None if v is None else 1e3 * v
