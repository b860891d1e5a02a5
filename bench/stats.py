"""Percentiles and the request-latency arithmetic the metrics share."""
from __future__ import annotations

import numpy as np


def p95(values) -> float | None:
    """95th percentile (linear interpolation between order statistics);
    None for no values."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def ttft_s(recs, seconds: float) -> list[float]:
    """Time to first token of every request due in the window [0, seconds),
    from its scheduled arrival. A request with no first token by the
    window's end enters as (window end - arrival): a stall stays in the
    tail instead of dropping out of it."""
    out = []
    for r in recs:
        if not 0.0 <= r.due < seconds:
            continue
        t = r.first_t if r.first_t is not None else seconds
        out.append(min(t, seconds) - r.due)
    return out


def tpot_s(recs, seconds: float) -> list[float]:
    """Per request completed inside the window: (last token - first token)
    / (output tokens - 1)."""
    return [(r.last_t - r.first_t) / (r.n - 1) for r in recs
            if r.done_t is not None and 0.0 <= r.done_t <= seconds
            and r.n > 1]


def queue_wait_s(recs, seconds: float) -> list[float]:
    """Due time to the start of the engine step that admitted the request,
    for every request due in the window; one still queued at the window's
    end enters as (window end - arrival)."""
    out = []
    for r in recs:
        if not 0.0 <= r.due < seconds:
            continue
        t = r.admit_t if r.admit_t is not None else seconds
        out.append(min(t, seconds) - r.due)
    return out
