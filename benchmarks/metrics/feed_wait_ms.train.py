"""Time the step loop waited for the feed, a step: the program's
`train/feed_wait` span over the traced window, by the steps dispatched in it."""

SPAN = "train/feed_wait"


def read(run):
    if SPAN not in run.spans or run.dispatched_steps <= 0:
        return None
    return run.spans[SPAN][1] * 1e3 / run.dispatched_steps
