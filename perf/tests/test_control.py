"""The control: the reference with crashed ops that never take effect
(a crashed write or cas treated as failed), put in the program's place.
It breaks the guarantee the configurations state, that an info op may
take effect once at any later time, and has to come out not correct.

At the cell's own size (on the chip host, three seeds or more):

    python3 perf/tests/test_control.py --workload cas1k_batch --seeds 1 2 3

prints, per seed, how many of the verdicts a run compares the control
gets wrong, over the whole pool a run's window cycles through.
"""

import argparse
import json
import time

if __name__ == "__main__":
    import conftest  # noqa: F401 — the same paths as under pytest

import cas_register as ref
import harness
import pytest
import run as perf_run

#: whole 1,000-op histories, so that each holds a crash or two
WHOLE = {"cas1k_batch": {"ops_per_history": 1000}, "keyed_test": None}


def control(check):
    """The control in the program's place: same inputs, same result
    shape, verdicts from the reference with crashed ops dropped."""
    def events(h):
        return [(op.type, op.process, op.f,
                 op.value.value if hasattr(op.value, "key") else op.value)
                for op in h]

    def controlled(prepared):
        if not hasattr(prepared[0], "check"):    # check_batch
            return [{"valid?": ref.linearizable(events(h), 0, False),
                     "engine": "control"} for h in prepared[1]]
        _checker, history = prepared             # keyed
        keys = {}
        for op in history:
            keys.setdefault(op.value.key, []).append(op)
        results = {k: {"valid?": ref.linearizable(events(h), 0, False),
                       "engine": "control"} for k, h in keys.items()}
        return {"valid?": all(r["valid?"] for r in results.values()),
                "results": results}
    return controlled


def reference_in_place(check):
    """The reference itself in the program's place: always correct."""
    inner = control(check)

    def sound(prepared):
        import cas_register

        real = cas_register.linearizable
        try:
            cas_register.linearizable = (
                lambda h, init, _take: real(h, init, True))
            return inner(prepared)
        finally:
            cas_register.linearizable = real
    return sound


@pytest.mark.parametrize("wl", sorted(WHOLE))
def test_control_is_not_correct(wl):
    for seed in (2**31 + 11, 2**31 + 12, 2**31 + 13):
        res = harness.run(wl, seed=seed, wrap=control, config=WHOLE[wl])
        assert res["correct"] is False, res["checks"]
        assert res["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("wl", ["cas1k_batch", "keyed_test"])
def test_reference_in_place_is_correct(wl):
    res = harness.run(wl, wrap=reference_in_place)
    assert res["correct"] is True, res["checks"]


def readings(workload, seed, shrunk=False, config=None):
    """Control mismatches over every verdict of the cell's pool, at the
    test size if ``shrunk``, with ``config`` merged over its
    configuration."""
    cell = harness.cell(workload, shrunk, config)
    ref = cell.module("reference")
    verify = perf_run.load_module("verify.py")
    pool = cell.module("generator").make_pool(cell.config, cell.traffic,
                                              seed)
    items = {(r, j): it for r, req in enumerate(pool)
             for j, it in enumerate(req)}
    want = verify.Expected(ref, cell.config, cell.traffic)
    got = verify.Expected(ref, cell.config, cell.traffic,
                          crashed_take_effect=False)
    answers = [(i, got.of(i, it)) for i, it in items.items()]
    mismatches, compared, _first = verify.compare(want, items, answers,
                                                  set())
    return {"workload": workload, "seed": seed, "mismatches": mismatches,
            "compared": compared}


#: (mismatches, verdicts compared) of the control as it read while the
#: CAS-register generator and labels were the harness's own, at the test
#: size with whole histories and at full size
CONTROL_PINNED = {
    ("cas1k_batch", "test_size"): {2**31 + 5: (11, 96), 2**31 + 11: (10, 96),
                                   3000000019: (9, 96)},
    ("keyed_test", "test_size"): {2**31 + 5: (0, 72), 2**31 + 11: (4, 72),
                                  3000000019: (3, 72)},
    ("cas1k_batch", "full"): {2400002611: (249, 2048)},
    ("keyed_test", "full"): {2400002611: (745, 19213)},
}


@pytest.mark.parametrize("wl,size", sorted(CONTROL_PINNED))
def test_control_readings_are_pinned(wl, size):
    shrunk = size == "test_size"
    for seed, (mismatches, compared) in CONTROL_PINNED[wl, size].items():
        row = readings(wl, seed, shrunk, WHOLE[wl] if shrunk else None)
        assert (row["mismatches"], row["compared"]) == (mismatches,
                                                        compared), seed


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    for s in a.seeds:
        t0 = time.perf_counter()
        row = readings(a.workload, s)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
