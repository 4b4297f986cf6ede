"""The whole job's share of the chip's bf16 peak: model FLOPs of the
window's jobs (`bench/work.py`) over window seconds x chips x peak."""

from bench import work


def read(run):
    flops = sum(work.job_flops(r, run.cfg) for r in run.jobs)
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peak["bf16_flops_per_s"])
