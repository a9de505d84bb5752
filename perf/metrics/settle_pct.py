"""Engine host: the share of the engine's wall time spent settling
retired dispatches and escalating overflows, 100 x engine/settle /
engine/pipeline, over the window."""

from _self_time import any_span
from _spans import seconds


def read(run):
    engine = seconds(run, "engine/pipeline")
    if not engine or not any_span(run, ("engine/settle",)):
        return None
    return 100.0 * seconds(run, "engine/settle") / engine
