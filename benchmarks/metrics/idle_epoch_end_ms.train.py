"""Device idle time a step at epoch boundaries: the first chip's idle gaps
between the last dispatch of one `train/epoch` span and the first dispatch of
the next (the loss flush, the reshuffle, a new feed thread's first window)."""

import scoped_trace


def read(run):
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    return scoped.idle_split()[0] * 1e3 / scoped_trace.steps(run)
