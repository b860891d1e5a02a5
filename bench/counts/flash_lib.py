"""Least time of the fused flash-attention kernel's calls in one engine step.

Per layer the tick makes one call per decode step over every slot, and an
admission makes one call over the prompts it prefills. A call's least time
is the larger of its flops over the peak rate and its bytes over the HBM
bandwidth, with flops and bytes from ``decode_attn`` / ``prefill_attn`` of
the architecture (filled positions only; MLA absorbed for decode). Prompts
admitted in one step are taken as one call, which can only lower the sum.
"""
from __future__ import annotations

from bench.counts.step import decode_steps


def least_s(arch, hf: dict, step: dict, peaks: dict) -> float:
    n_layers = hf["num_hidden_layers"]
    rate, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    t = 0.0
    for j in range(decode_steps(step)):
        f = b = 0
        for pos, n in step["live"]:
            if j < n:
                df, db = arch.decode_attn(hf, pos + j + 1)
                f, b = f + df, b + db
        t += n_layers * max(f / rate, b / bw)
    if step["admitted"]:
        f = b = 0
        for p in step["admitted"]:
            df, db = arch.prefill_attn(hf, p)
            f, b = f + df, b + db
        t += n_layers * max(f / rate, b / bw)
    return t
