"""Prediction seconds per job: host clock around `decision_function` and
`predict` on the test rows (both end in a host copy)."""


def read(run):
    return sum(r.predict_s for r in run.jobs) / len(run.jobs)
