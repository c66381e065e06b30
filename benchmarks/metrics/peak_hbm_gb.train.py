"""Peak device memory after the window, on the fullest chip."""


def read(run):
    return run.memory_peak_bytes / 1e9
