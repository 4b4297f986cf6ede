"""Peak device memory in use over the run, on the fullest chip
(`memory_stats()["peak_bytes_in_use"]`, read after the window)."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
