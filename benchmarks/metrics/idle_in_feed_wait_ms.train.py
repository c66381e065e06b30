"""Device idle time a step while the step loop waited for the feed: the part
of the first chip's idle gaps that lies under a `train/feed_wait` span, the
gaps at epoch boundaries apart (`idle_epoch_end_ms.train` has those)."""

import scoped_trace


def read(run):
    scoped = scoped_trace.load(run)
    if scoped is None:
        return None
    return scoped.idle_split()[1] * 1e3 / scoped_trace.steps(run)
