"""The latency arithmetic: percentile, censored time to first token, mean
token gap, queue wait."""
from __future__ import annotations

import numpy as np
import pytest

from bench import stats
from bench.harness import Rec


def rec(due, first=None, last=None, done=None, n=0, admit=None):
    return Rec(0, due, np.zeros(4, np.int32), 8, admit_t=admit,
               first_t=first, last_t=last, done_t=done, n=n)


def test_p95_interpolates_and_empty_is_none():
    assert stats.p95(range(1, 101)) == pytest.approx(95.05)
    assert stats.p95([]) is None
    assert stats.p95([3.0]) == 3.0


def test_ttft_counts_only_requests_due_in_the_window():
    recs = [rec(-1.0, first=0.5), rec(1.0, first=1.25), rec(10.0, first=11)]
    assert stats.ttft_s(recs, 10.0) == [0.25]


def test_ttft_censors_a_request_without_first_token_at_window_end():
    recs = [rec(2.0), rec(7.0, first=12.0), rec(3.0, first=3.5)]
    # no first token: window end - arrival; a late first token is clipped
    assert stats.ttft_s(recs, 10.0) == [8.0, 3.0, 0.5]


def test_tpot_is_the_mean_gap_of_requests_completed_in_the_window():
    recs = [rec(0.0, first=1.0, last=2.0, done=2.0, n=11),
            rec(0.0, first=1.0, last=12.0, done=12.0, n=11),  # after window
            rec(0.0, first=1.0, last=1.0, done=1.0, n=1),  # one token
            rec(0.0, first=1.0, last=3.0, n=5)]  # not done
    assert stats.tpot_s(recs, 10.0) == [pytest.approx(0.1)]


def test_queue_wait_censors_a_request_still_queued():
    recs = [rec(1.0, admit=1.5), rec(4.0), rec(-2.0, admit=0.0)]
    assert stats.queue_wait_s(recs, 10.0) == [0.5, 6.0]


def _run(steps, seconds=10.0):
    from bench.harness import Run

    return Run(None, seconds, [], steps, 0.0, {}, None)


def _step(t0, t1, tokens):
    return {"t0": t0, "t1": t1, "steps": 1, "live": [], "admitted": [],
            "tokens": tokens}


def test_window_is_whole_engine_steps():
    # a step cut by the nominal start, three inside, one cut by the end
    steps = [_step(-2.0, -0.5, 5), _step(-0.5, 0.5, 7), _step(0.5, 4.0, 9),
             _step(4.0, 9.5, 11), _step(9.5, 10.75, 13)]
    run = _run(steps)
    assert run.window() == (-0.5, 10.75)
    assert run.window_s() == 11.25
    assert [s["tokens"] for s in run.window_steps()] == [7, 9, 11, 13]


def test_window_keeps_idle_time_to_the_nominal_end():
    # nothing ended by the start, and the engine slept after its last step
    run = _run([_step(0.25, 1.0, 3), _step(1.0, 2.0, 4)])
    assert run.window() == (0.0, 10.0)
    assert [s["tokens"] for s in run.window_steps()] == [3, 4]
    assert _run([]).window() == (0.0, 10.0)
