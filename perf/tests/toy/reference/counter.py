"""The plain reference for a counter history, as
``jepsen.checker/counter`` states it: every ok read lies between the
sum of the adds that completed ok before the read was invoked and the
sum of the adds invoked, and not failed, before it completed."""

from __future__ import annotations


def valid(history):
    completion = {}
    open_by_pid = {}
    for i, (t, p, _f, _v) in enumerate(history):
        if t == "invoke":
            open_by_pid[p] = i
        elif p in open_by_pid:
            completion[open_by_pid.pop(p)] = t
    lower = upper = 0
    floor = {}
    for i, (t, p, f, v) in enumerate(history):
        if f == "add":
            if t == "invoke" and completion.get(i) != "fail":
                upper += v
            elif t == "ok":
                lower += v
        elif f == "read":
            if t == "invoke":
                floor[p] = lower
            elif t == "ok" and not floor.pop(p) <= v <= upper:
                return False
    return True


def known(cfg, traffic, item):
    """What the generator knows: an honest history is valid, and one
    whose ok read exceeds every add it could have summed is not."""
    most = cfg["max_add"] * cfg["ops_per_history"]
    return {"valid?": all(not (t == "ok" and f == "read" and v > most)
                          for t, _p, f, v in item)}


def search(cfg, traffic, item, labels):
    return {"valid?": valid(item)}
