"""Entry: ``workloads.cycle.append.checker(...).check(test, history,
{})``, one request one Jepsen list-append test: the analysis a run of
``jepsen.tests.cycle.append`` ends with, Elle's list-append checker
under the configuration's consistency models and screen route.  The
test map has no store identity, so the checker writes no artifacts."""

from __future__ import annotations

from jepsen_tpu.elle import cycles

# The configuration screens every dependency SCC of up to
# DEVICE_SCREEN_MAX_VERTICES vertices on the device.  A program without
# the SCC split sends each of this configuration's graphs (thousands of
# vertices) whole to the CPU, so the device would never run: refuse it
# here, before the pool is made, and the run exits at once.
if not hasattr(cycles, "scc_parts"):
    raise ImportError("the program's Elle checker does not split a graph "
                      "into its SCCs (elle.cycles.scc_parts): it cannot "
                      "screen this configuration's graphs on the device")


def prepare(cfg, items):
    """One test's history as the program's ops, its checker, and the
    configuration's labels."""
    from jepsen_tpu.history import History, Op
    from jepsen_tpu.workloads.cycle import append

    ((_faulty, events),) = items
    history = History([Op(t, p, "txn", v, time=ts)
                       for t, p, v, ts in events]).index_ops()
    checker = append.checker({"consistency-models":
                              list(cfg["consistency_models"]),
                              "screen-route": cfg["screen_route"]})
    return checker, history, tuple(cfg["labels"])


def check(prepared):
    checker, history, labels = prepared
    return labels, checker.check({}, history, {})


def rows(checked, n_items):
    """``valid?`` and one boolean per anomaly name of the
    configuration's labels, each true where the result reports it;
    the result carries no per-SCC rows."""
    labels, result = checked
    found = set(result.get("anomaly-types", ())) | set(
        result.get("also-anomaly-types", ()))
    verdicts = {"valid?": result.get("valid?")}
    for name in labels:
        verdicts[name] = name in found
    return [verdicts], []
