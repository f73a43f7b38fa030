"""Peak device memory in use (GiB), read after the window: weights,
lane state and every temporary the serving programs needed."""


def read(run):
    # None where the backend keeps no memory statistics (the CPU)
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
