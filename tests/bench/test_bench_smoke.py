"""A smoke-size cell driven end to end through the harness on the CPU:
weights from the seed, engine with AOT buckets, warm-up, open-loop window,
metrics, and the correctness check against the plain reference."""
from __future__ import annotations

import json
import time

import pytest

from bench import harness


@pytest.mark.parametrize("workload", ["yi_smoke.interp.tiny",
                                      "minicpm_smoke.interp.tiny",
                                      "yi_smoke.exact.tiny"])
def test_smoke_cell_runs_and_is_correct(smoke_root, workload):
    r = harness.run(workload, 2**33 + 7, 1.5, False, time.perf_counter(),
                    smoke_root, smoke_root / "record.json")
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"output_tokens_per_s", "ttft_p95_ms",
                                 "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)  # the result line is plain JSON
    rec = json.loads((smoke_root / "record.json").read_text())
    assert rec["steps"] and sum(0 <= q["due"] < 1.5 for q in rec["recs"]) \
        == r["attempted"]


def test_same_seed_same_traffic_and_weights(smoke_root):
    import numpy as np

    from bench import traffic

    cell = harness.load_cell("yi_smoke.interp.tiny", smoke_root)
    a = traffic.arrivals(cell.mix, 8.0, 2.0, 2**40 + 1, 256)
    b = traffic.arrivals(cell.mix, 8.0, 2.0, 2**40 + 1, 256)
    c = traffic.arrivals(cell.mix, 8.0, 2.0, 1, 256)
    assert [(x.due, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new, x.prompt.tolist()) for x in b]
    # another seed: the same schedule, other token ids
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    w1 = harness.make_weights(cell, 2**40 + 1)
    w2 = harness.make_weights(cell, 2**40 + 1)
    w3 = harness.make_weights(cell, 1)
    tok = lambda w: np.asarray(w["embed"]["tok"])  # noqa: E731
    assert np.array_equal(tok(w1), tok(w2))
    assert not np.array_equal(tok(w1), tok(w3))
