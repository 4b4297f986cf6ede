"""SMO kernel calls per job on the streamed stage-2 route
(`Stage2StreamStats.kernel_calls`); nothing where stage 2 did not stream."""


def read(run):
    calls = [r.kernel_calls for r in run.jobs]
    return sum(calls) / len(calls) if any(calls) else None
