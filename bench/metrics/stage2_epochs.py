"""Epochs of stage 2 to tolerance: the largest `SolveResult.epochs` over
tasks, per job (a count)."""


def read(run):
    return sum(max(r.epochs) for r in run.jobs) / len(run.jobs)
