"""Stage 1 (landmarks, eigh, G), seconds per job: `FitStats.stage1_seconds`,
host clock around work that ends in a blocking wait on G."""


def read(run):
    return sum(r.stage1_s for r in run.jobs) / len(run.jobs)
