"""Process start to window start: imports, data, the warm-up job and its
compilations or cache loads (host clock)."""


def read(run):
    return run.setup_s
