"""What the Elle metrics share: the checker's spans over the window's
request wall time, and its SCC counters."""

from _self_time import any_span
from _spans import seconds


def span_pct(run, name):
    """100 x the summed ``name`` spans / the summed request wall time,
    or None where the program records no such span."""
    wall = sum(r.t1 - r.t0 for r in run.requests)
    if not wall or not any_span(run, (name,)):
        return None
    return 100.0 * seconds(run, name) / wall


def counter_by_label(run, name, label):
    """{label value: count} of counter ``name``, empty if never
    counted."""
    out = {}
    for m in run.obs_metrics:
        if m["kind"] == "counter" and m["name"] == name:
            key = m["labels"].get(label)
            out[key] = out.get(key, 0) + m["value"]
    return out
