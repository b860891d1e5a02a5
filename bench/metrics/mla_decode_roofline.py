"""Kernels: the absorbed-latent MLA decode kernel's share of its roofline in
the traced window: the least time of its calls over their device time in
the trace. Percent.

A call is one layer of one decode step over every slot. Its least time is
the larger of its flops over the peak rate and its bytes over the HBM
bandwidth, with flops and bytes from the architecture's ``decode_attn``
(``bench/counts``: MLA counted absorbed) summed over the filled positions
of the slots live in that step. The kernel is found by its name,
``mla_flash_lib``; a program without it reads nothing."""
from bench import trace
from bench.counts.step import decode_steps

KERNEL = "mla_flash_lib"


def is_mla_decode(name, _operands):
    return name == KERNEL


def least_s(arch, hf: dict, step: dict, peaks: dict) -> float:
    """Least time of one engine step's absorbed decode calls."""
    rate, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    t = 0.0
    for j in range(decode_steps(step)):
        f = b = 0
        for pos, n in step["live"]:
            if j < n:
                df, db = arch.decode_attn(hf, pos + j + 1)
                f, b = f + df, b + db
        t += hf["num_hidden_layers"] * max(f / rate, b / bw)
    return t


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace.window(run.trace)
    t = trace.op_time_ns(run.trace["devices"][0], is_mla_decode, lo,
                         hi) / 1e9
    if t <= 0:
        return None
    least = sum(least_s(run.counts, run.hf, s, run.peaks)
                for s in run.traced_steps())
    return 100.0 * least / t
