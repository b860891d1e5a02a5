"""Model step: model flops of every prompt prefilled and every token decoded
in the window (least work from shapes, ``bench/counts``), over the window's
length (whole engine steps, ``Run.window``) times the chip's bf16 peak.
Percent."""
from bench.counts import step


def read(run):
    f = sum(step.flops(run.counts, run.hf, s) for s in run.window_steps())
    return 100.0 * f / (run.window_s() * run.peaks["bf16_flops_per_s"]
                        * run.cell.chips)
