"""Arithmetic over the program's nested obs spans: summed durations,
attributes and self time.  A span's parent is the span that enclosed
it on its own thread, so its children lie inside its interval."""


def finished(run, name):
    """The finished spans called ``name``."""
    return [s for s in run.spans
            if s["name"] == name and s.get("t1") is not None]


def any_span(run, names):
    """True if the run recorded a finished span of any of ``names``:
    a program without them (an older one) leaves a metric unread."""
    return any(s["name"] in names and s.get("t1") is not None
               for s in run.spans)


def attr_sum(spans, key):
    """Summed integer attribute ``key`` over ``spans``."""
    return sum(int(s.get("attrs", {}).get(key, 0) or 0) for s in spans)


def covered(lo, hi, intervals):
    """Length of the part of [lo, hi) that the union of ``intervals``
    covers."""
    total = 0
    end = lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def self_time_ns(run, name):
    """(summed duration, summed self time) in ns of the finished spans
    called ``name``; self time is the part of a span's interval that
    none of its children covers."""
    kids = {}
    for s in run.spans:
        if s.get("parent") is not None and s.get("t1") is not None:
            kids.setdefault((s.get("pid"), s["parent"]), []).append(
                (s["t0"], s["t1"]))
    total = own = 0
    for s in finished(run, name):
        lo, hi = s["t0"], s["t1"]
        total += hi - lo
        own += hi - lo - covered(lo, hi, kids.get((s.get("pid"), s["sid"]),
                                                  ()))
    return total, own
