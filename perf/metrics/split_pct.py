"""Checker seam: the share of each keyed test's wall time spent
splitting the history into per-key sub-histories, 100 x checker/split
/ request wall, over the window."""

from _self_time import any_span
from _spans import seconds


def read(run):
    wall = sum(r.t1 - r.t0 for r in run.requests)
    if not wall or not any_span(run, ("checker/split",)):
        return None
    return 100.0 * seconds(run, "checker/split") / wall
