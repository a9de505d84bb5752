"""The ``elle_append_test`` cell at the test size: its pool, its
control and its traced metrics.

The control is the reference with the realtime edges dropped, put in
the program's place: it checks sequential consistency where the
configuration asks for strict serializability, misses the cycles a
lagging replica closes through realtime order, and has to come out
not correct.  At the cell's own size (on the chip host, three seeds
or more):

    python3 perf/tests/test_elle_append.py --seeds 1 2 3

prints, per seed, how many of the verdicts a run compares the control
gets wrong, over the whole pool a run's window cycles through.
"""

import argparse
import hashlib
import json
import time

if __name__ == "__main__":
    import conftest  # noqa: F401 — the same paths as under pytest

import harness
import pytest
import run as perf_run

CELL = "elle_append_test"
#: sha256 of the pool's JSON, as the generator first made it, by
#: (size, seed)
POOL_PINNED = {
    ("test_size", 2**31 + 5):
        "d0839ffe67aa432b3c91028d3011fe51dbe6736ce99a1818c222b916fd5b83e3",
    ("test_size", 3000000019):
        "a5c60d628fa7f4015d5321ebc83786385346cf92d82054cc88c12eb1cc788f2d",
    ("full", 2600002701):
        "f1dcfd05f177e3e7375890d0f87550289d6fde7ac995f9c7675b0f694008152c",
}


def pool_hash(shrunk, seed):
    cell = harness.cell(CELL, shrunk)
    pool = cell.module("generator").make_pool(cell.config, cell.traffic,
                                              seed)
    return hashlib.sha256(json.dumps(pool).encode()).hexdigest()


@pytest.mark.parametrize("size,seed", sorted(POOL_PINNED))
def test_pool_is_pinned(size, seed):
    got = pool_hash(size == "test_size", seed)
    assert got == pool_hash(size == "test_size", seed)
    assert got == POOL_PINNED[size, seed]


def in_place(realtime):
    """The reference in the program's place: the result a checker
    would give, from ``analyse`` with or without realtime edges."""
    def wrap(check):
        def controlled(prepared):
            ref = harness.cell(CELL).module("reference")
            _checker, history, labels = prepared
            got = ref.analyse([(op.type, op.process, op.value, op.time)
                               for op in history], realtime)
            return labels, {"valid?": got.pop("valid?"),
                            "anomaly-types": [k for k, v in got.items()
                                              if v]}
        return controlled
    return wrap


#: seeds whose test-size pool's one faulty test holds a cycle that only
#: realtime order closes (a faulty test's cycles often close without it)
CONTROL_SEEDS = (2**31 + 5, 2**31 + 12, 2**31 + 13)


def test_control_is_not_correct():
    for seed in CONTROL_SEEDS:
        res = harness.run(CELL, seed=seed, wrap=in_place(False))
        assert res["correct"] is False, res["checks"]
        assert res["checks"]["verdict_mismatches"]["value"] > 0


def test_reference_in_place_is_correct():
    res = harness.run(CELL, wrap=in_place(True))
    assert res["correct"] is True, res["checks"]


def test_a_traced_run_reports_the_elle_metrics():
    res = harness.run(CELL, trace=1)
    assert res["correct"] is True, res["checks"]
    assert {"elle_infer_pct", "elle_screen_pct", "elle_search_pct",
            "elle_device_vertex_pct",
            "window_compiles.elle_append"} <= set(res["metrics"])
    assert res["metrics"]["elle_device_vertex_pct"]["value"] > 0


def test_a_program_without_the_scc_split_is_refused(monkeypatch, capsys):
    """A program that cannot split a graph into its SCCs would screen
    none of the cell's graphs on the device: the run exits 2 before it
    starts jax or makes the pool."""
    from jepsen_tpu.elle import cycles

    harness.cell(CELL).module("entry")   # the program as it is
    argv = ["--workload", CELL, "--seed", str(2**31 + 5), "--seconds",
            "0.5", "--trace", "1"]
    monkeypatch.delattr(cycles, "scc_parts")
    monkeypatch.setattr(perf_run, "start_jax", None)   # never reached
    assert perf_run.main(argv) == 2
    assert "elle.cycles.scc_parts" in capsys.readouterr().err


def test_elle_readers_on_a_made_up_run():
    """The roofline counts the SCCs screened inside the traced
    requests, against the screen executable's device time alone; the
    device's vertex share is over every route."""
    from types import SimpleNamespace as NS

    import closure_roofline_pct
    import elle_device_vertex_pct

    req = NS(mono0=1000, t0=0.0, t1=1e-6, error=None)   # 1,000 ns
    run = NS(
        profile={"requests": [(0, 1000, 0)], "chips": [{"modules": {
            "jit_screen(3)": [2000, 1], "jit_check_one(1)": [500, 1]}}]},
        requests=[req], peaks={"bf16_flops_per_s": 1e12},
        spans=[{"name": "elle/screen", "t0": 1500, "t1": 1900,
                "attrs": {"scc_vertices": "10,4", "planes": "3,1",
                          "walks": "2,0"}},
               {"name": "elle/screen", "t0": 5000, "t1": 5100,
                "attrs": {"scc_vertices": "500", "planes": "6",
                          "walks": "2"}}],
        obs_metrics=[
            {"kind": "counter", "name": "jepsen_elle_scc_vertices_total",
             "labels": {"route": "device"}, "value": 30},
            {"kind": "counter", "name": "jepsen_elle_scc_vertices_total",
             "labels": {"route": "cpu"}, "value": 10}])
    # 2 n^3 a plane, 2 (2n)^3 a walk: 6,000 + 32,000 + 128
    least_s = 38128 / 1e12
    assert closure_roofline_pct.read(run) == pytest.approx(
        100 * least_s / 2e-6)
    assert elle_device_vertex_pct.read(run) == 75.0
    run.obs_metrics = []
    run.profile = None
    assert closure_roofline_pct.read(run) is None
    assert elle_device_vertex_pct.read(run) is None


def readings(seed, shrunk=False):
    """Control mismatches over every verdict of the cell's pool."""
    cell = harness.cell(CELL, shrunk)
    ref = cell.module("reference")
    verify = perf_run.load_module("verify.py")
    pool = cell.module("generator").make_pool(cell.config, cell.traffic,
                                              seed)
    items = {(r, j): it for r, req in enumerate(pool)
             for j, it in enumerate(req)}
    want = verify.Expected(ref, cell.config, cell.traffic)
    got = verify.Expected(ref, cell.config, cell.traffic, realtime=False)
    answers = [(i, got.of(i, it)) for i, it in items.items()]
    mismatches, compared, _first = verify.compare(want, items, answers,
                                                  set())
    return {"workload": CELL, "seed": seed, "mismatches": mismatches,
            "compared": compared}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    for s in a.seeds:
        t0 = time.perf_counter()
        row = readings(s)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
