"""Passes over its bound on rows that the fullest routed layer made in the
window's last step: the model's state leaf `row_passes` (`parallel/moe.py`;
the decoder keeps its layers' largest), fetched once after the window. 1.0
where every layer's held pairs fit twice their balanced expectation; above it
the step's device work follows the routing, and the run is no sound
measurement of the cell (PERF.md, section 3)."""


def read(run):
    passes = [v for path, v in run.state.items() if path.rsplit("/", 1)[-1] == "row_passes"]
    return max(passes) if passes else None
