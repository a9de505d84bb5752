"""Entry: ``jepsen_tpu.checker.counter()`` over each history of a
request."""

from __future__ import annotations


def prepare(cfg, items):
    from jepsen_tpu import checker
    from jepsen_tpu.history import History, Op

    return checker.counter(), [
        History([Op(t, p, f, v) for t, p, f, v in events]).index_ops()
        for events in items]


def check(prepared):
    chk, hists = prepared
    return [chk.check({}, h) for h in hists]


def rows(results, n_items):
    """Per item {"valid?": verdict}; the checker runs no device rows."""
    verdicts = [{} for _ in range(n_items)]
    for i, r in enumerate(results[:n_items]):
        verdicts[i]["valid?"] = r.get("valid?")
    return verdicts, []
