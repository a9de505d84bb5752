"""Engine host: host nanoseconds to encode one history event,
sum of engine/encode spans / sum of their ``events`` attribute (the
history's event count, 0 if it was unencodable), over the window."""

from _self_time import attr_sum, finished


def read(run):
    spans = finished(run, "engine/encode")
    events = attr_sum(spans, "events")
    if not events:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / events
