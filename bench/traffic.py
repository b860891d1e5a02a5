"""One general generator of open-loop traffic from a mix file.

A mix (``bench/mixes/<name>.json``) gives the arrival process and the
length distributions; the cell gives the rate. The schedule -- inter-arrival
gaps, prompt lengths and output lengths, the quantiles (i + 0.5) / N of
each distribution, each set shuffled on its own -- is drawn from the mix's
``schedule_seed`` and is the same for every run seed; the run seed draws the
token ids (and the weights). Within a window of some tens of requests the
order of a heavy-tailed set decides how much work the window holds, so a
per-seed order would make the seed change the work.

Mix keys:

- ``arrivals``: ``{"kind": "poisson"}``.
- ``prompt_tokens``, ``output_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``.
- ``lead_s``: seconds of the same traffic before the measured window, so the
  window opens on a loaded system (counted as set-up).
- ``schedule_seed``: the seed of the schedule (default 0).
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float  # seconds from the window's start (negative: lead-in)
    prompt: np.ndarray  # (S,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n quantile lengths of a length distribution, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv(float(p)) for p in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """The schedule of one run: every request due in [-lead_s, seconds)."""
    lead = float(mix.get("lead_s", 0.0))
    span = lead + seconds
    n = max(1, math.ceil(rate * span))
    shape = np.random.default_rng([int(mix.get("schedule_seed", 0)), 2])
    rng = np.random.default_rng([seed % (1 << 64), 2])
    if mix["arrivals"]["kind"] != "poisson":
        raise ValueError(f"unknown arrival process "
                         f"{mix['arrivals']['kind']!r}")
    gaps = -np.log1p(-_quantiles(n)) / rate
    t = np.cumsum(shape.permutation(gaps)) - lead
    prompts = shape.permutation(lengths(mix["prompt_tokens"], n))
    outs = shape.permutation(lengths(mix["output_tokens"], n))
    out = []
    for i in range(n):
        if t[i] >= seconds:
            continue
        toks = rng.integers(0, vocab, int(prompts[i]), dtype=np.int64)
        out.append(Arrival(i, float(t[i]), toks.astype(np.int32),
                           int(outs[i])))
    return out
