"""Engine host: the share of the engine's wall time that no span inside
it accounts for, 100 x self time of engine/pipeline / its duration,
over the window.  Read only where the program has the engine's inner
spans (encode, plan, issue, settle)."""

from _self_time import any_span, self_time_ns

INNER = ("engine/encode", "engine/plan", "engine/issue", "engine/settle")


def read(run):
    if not any_span(run, INNER):
        return None
    total, own = self_time_ns(run, "engine/pipeline")
    return 100.0 * own / total if total else None
