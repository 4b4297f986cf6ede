"""Stage 2 (dual coordinate ascent over G), seconds per job:
`FitStats.stage2_seconds`, host clock around work that ends in a blocking
wait on W."""


def read(run):
    return sum(r.stage2_s for r in run.jobs) / len(run.jobs)
