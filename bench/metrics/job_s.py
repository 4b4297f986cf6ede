"""Seconds per trained and scored model: window seconds over jobs (host
clock; every job ends in a blocking read of its labels)."""


def read(run):
    return run.window_s / len(run.jobs)
