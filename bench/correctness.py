"""Whether the timed path served the right tokens.

After the window, a sample of the finished requests, drawn from the seed and
always holding the one with the most served tokens, is run once through the
plain float32 reference of the architecture (``bench/reference/<arch>.py``)
over its prompt followed by its served tokens. For every served token the
gap by which the reference's logit of that token lies below the reference's
best logit at that position is read; the compared number is the widest gap
over the sample (``max_logit_gap``). Greedy decoding serves the argmax of
the program's own logits, so a sound program serves only tokens whose
reference logit is within its rounding of the best.

The reference makes its weights again from the seed with the benchmark's own
generator; it takes nothing the program made.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import weights


def sample(finished: list, seed: int, n_requests: int) -> list:
    """``n_requests`` finished requests: the one with the most served
    tokens, then others in an order drawn from the seed."""
    if not finished:
        return []
    by_rid = sorted(finished, key=lambda r: r.rid)
    longest = max(by_rid, key=lambda r: len(r.req.out))
    rest = [r for r in by_rid if r is not longest]
    rng = np.random.default_rng([seed % (1 << 64), 4])
    order = rng.permutation(len(rest))
    return [longest] + [rest[i] for i in order[: n_requests - 1]]


def gaps(ref, hf: dict, params, prompt, served, quant=None) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the token chosen -- the served token, or with ``quant`` the argmax of
    the reference computed at that lower precision (the control)."""
    served = np.asarray(served, np.int64)
    seq = np.concatenate([np.asarray(prompt, np.int64), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    exact = np.asarray(ref.logits(params, hf, seq, rows), np.float64)
    if quant is None:
        chosen = served
    else:
        low = np.asarray(ref.logits(params, hf, seq, rows, quant=quant))
        chosen = np.argmax(low, axis=-1)
    return exact.max(-1) - exact[np.arange(len(served)), chosen]


def check(cell, finished: list, seed: int, quant=None) -> dict:
    """The compared numbers of a run, each with its limit."""
    from bench.harness import module

    ref = module("reference", cell.arch, cell.bench_dir)
    chk = cell.mix["check"]
    picked = sample(finished, seed, chk["requests"])
    worst = None
    if picked:
        params = weights.make(ref.param_shapes(cell.hf), seed)
        worst = max(float(gaps(ref, cell.hf, params, r.prompt, r.req.out,
                               quant).max()) for r in picked)
        del params
    n_tok = sum(len(r.req.out) for r in picked)
    print(f"correctness sample: {len(picked)} requests, {n_tok} served "
          f"tokens", file=sys.stderr, flush=True)
    return {"max_logit_gap": {"value": worst,
                              "limit": cell.params["limits"]["max_logit_gap"]}}
