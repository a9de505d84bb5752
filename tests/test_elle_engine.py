"""Engine-routed transactional checking (the Elle screens).

Pins the PR's contracts:

- op-soup fuzz (>500 cases): device-screened ``classify`` /
  ``consistency`` verdicts byte-identical to the pure-CPU path across
  list-append and rw-register workloads, cyclic and acyclic, all
  relation filters (plain, process, realtime);
- ``has_cycle_batch`` respects the calibrated row budget (the engine's
  per-chip cap — it historically had none);
- screen buckets ride the production Executor (window, chunking,
  accounting) and rank through ``planning.estimated_cost`` /
  the tune cost table;
- partition-aware cost scheduling: global largest-cost-first at
  pipeline finish and across daemon groups;
- the ``/elle`` service seam round-trips screens byte-identically.
"""

import json
import random

import numpy as np
import pytest

from jepsen_tpu import elle
from jepsen_tpu.elle import cycles as elle_cycles
from jepsen_tpu.elle import encode as elle_encode
from jepsen_tpu.elle.graph import Graph
from jepsen_tpu.engine import execution, planning
from jepsen_tpu.history import History, Op
from jepsen_tpu.ops import cycles as ops_cycles


# ---------------------------------------------------------------------------
# corpus generation: deterministic op soup with seeded corruption
# ---------------------------------------------------------------------------


def _soup_history(rng: random.Random, mode: str, n_txns: int,
                  n_keys: int, corrupt: bool) -> History:
    """A transaction history against a serializable in-memory store,
    with seeded corruptions (stale/duplicated/truncated reads, failed
    writers whose values leak) and an occasional injected committed
    wr-dependency cycle — the op-soup style that validated the direct
    checkers."""
    lists = {k: [] for k in range(n_keys)}
    regs = {k: None for k in range(n_keys)}
    next_val = [1]
    dicts = []
    t = [0]

    def emit(process, txn, typ="ok"):
        dicts.append({"process": process, "type": "invoke", "f": "txn",
                      "value": [[f, k, None if f == "r" else v]
                                for f, k, v in txn],
                      "time": t[0]})
        t[0] += 5
        dicts.append({"process": process, "type": typ, "f": "txn",
                      "value": txn, "time": t[0]})
        t[0] += 5

    for i in range(n_txns):
        p = rng.randrange(4)
        txn = []
        failed = corrupt and rng.random() < 0.08
        for _m in range(rng.randrange(1, 4)):
            k = rng.randrange(n_keys)
            if rng.random() < 0.5:
                v = next_val[0]
                next_val[0] += 1
                if mode == "list-append":
                    txn.append(["append", k, v])
                    if not failed:
                        lists[k] = lists[k] + [v]
                else:
                    txn.append(["w", k, v])
                    if not failed:
                        regs[k] = v
            else:
                if mode == "list-append":
                    v = list(lists[k])
                    if corrupt and v and rng.random() < 0.25:
                        mut = rng.random()
                        if mut < 0.3:
                            v = v[:-1]  # truncated (intermediate) read
                        elif mut < 0.6:
                            v = v + [v[-1]]  # duplicate element
                        else:
                            v = list(reversed(v))  # incompatible order
                    txn.append(["r", k, v])
                else:
                    v = regs[k]
                    if corrupt and rng.random() < 0.25:
                        v = rng.randrange(1, max(2, next_val[0]))  # stale
                    txn.append(["r", k, v])
        emit(p, txn, "fail" if failed else "ok")

    if corrupt and rng.random() < 0.35:
        # guaranteed committed dependency cycle on fresh keys (G1c)
        kx, ky = n_keys, n_keys + 1
        if mode == "list-append":
            t1 = [["append", kx, 1], ["r", ky, [2]]]
            t2 = [["append", ky, 2], ["r", kx, [1]]]
        else:
            t1 = [["w", kx, 1], ["r", ky, 2]]
            t2 = [["w", ky, 2], ["r", kx, 1]]
        emit(91, t1)
        emit(92, t2)
    return History([Op.from_dict(d) for d in dicts]).index_ops()


_MODEL_SETS = (
    ["serializable"],
    ["snapshot-isolation"],
    ["read-committed"],
    ["strict-serializable"],  # realtime graphs → suffixed filters
    ["sequential"],           # process graphs → suffixed filters
)


def _dumps(x):
    return json.dumps(x, sort_keys=True, default=repr)


def test_op_soup_fuzz_screened_byte_identical():
    """≥500 fuzz cases: device-screened classify/consistency verdicts
    byte-identical to the pure-CPU path across both workloads, cyclic
    and acyclic corpora, and every relation-filter family."""
    rng = random.Random(45100)
    cases = 0
    mismatches = []
    for mode in ("list-append", "rw-register"):
        hists = [
            _soup_history(rng, mode, rng.randrange(3, 14), 3,
                          corrupt=(i % 2 == 0))
            for i in range(52)
        ]
        for models in _MODEL_SETS:
            opts = {"workload": mode, "consistency-models": models}
            cpu = elle.check_batch({**opts, "screen-route": "cpu"}, hists)
            dev = elle.check_batch({**opts, "screen-route": "device"},
                                   hists)
            cases += len(hists)
            for h_i, (a, b) in enumerate(zip(cpu, dev)):
                if _dumps(a) != _dumps(b):
                    mismatches.append((mode, models[0], h_i))
        # sanity: the corpus genuinely mixes verdicts
        base = elle.check_batch(
            {"workload": mode, "consistency-models": ["serializable"],
             "screen-route": "cpu"}, hists,
        )
        verdicts = {r["valid?"] for r in base}
        assert True in verdicts and (False in verdicts
                                     or "unknown" in verdicts), verdicts
    assert cases >= 500, cases
    assert not mismatches, mismatches[:5]


def test_check_batch_matches_per_history_check():
    rng = random.Random(7)
    hists = [_soup_history(rng, "rw-register", 6, 2, corrupt=True)
             for _ in range(6)]
    opts = {"workload": "rw-register",
            "consistency-models": ["serializable"]}
    batch = elle.check_batch({**opts, "screen-route": "cpu"}, hists)
    single = [elle.check({**opts, "screen-route": "cpu"}, h)
              for h in hists]
    assert _dumps(batch) == _dumps(single)


# ---------------------------------------------------------------------------
# budget + engine routing
# ---------------------------------------------------------------------------


def _ring_mats(count, n):
    mats = []
    for i in range(count):
        a = np.zeros((n, n), bool)
        for j in range(n - 1):
            a[j, j + 1] = True
        if i % 2 == 0:
            a[n - 1, 0] = True
        mats.append(a)
    return mats


def test_has_cycle_batch_respects_row_budget(monkeypatch):
    """The calibrated-row-budget regression: a batch far beyond the
    per-dispatch cap must chunk through the executor with per-chip
    in-flight rows never exceeding the cap — has_cycle_batch
    historically dispatched everything in one unbounded shot."""
    monkeypatch.setattr(ops_cycles, "CYCLES_DISPATCH_BUDGET", 4096)
    n = 16  # per_row = 16*16*2 = 512 words → cap 8
    assert ops_cycles.cycles_max_dispatch(n) == 8
    mats = _ring_mats(30, n - 3)
    ex = execution.Executor(1, mesh=None)
    got = ops_cycles.has_cycle_batch(mats, executor=ex)
    assert list(got) == [i % 2 == 0 for i in range(30)]
    assert ex.submitted == 4  # ceil(30 / 8) chunks
    for acct in ex.chip_row_accounting.values():
        assert acct["peak_chip_rows"] <= 8, acct
    # windowed: frontier-style 1/W split keeps total in flight ≤ cap
    monkeypatch.setattr(ops_cycles, "CYCLES_DISPATCH_BUDGET", 4096)
    ex4 = execution.Executor(4, mesh=None)
    got = ops_cycles.has_cycle_batch(mats, executor=ex4)
    assert list(got) == [i % 2 == 0 for i in range(30)]
    for acct in ex4.chip_row_accounting.values():
        assert acct["peak_chip_rows"] <= 8, acct


def test_has_cycle_batch_over_budget_falls_to_host(monkeypatch):
    monkeypatch.setattr(ops_cycles, "CYCLES_DISPATCH_BUDGET", 100)
    mats = _ring_mats(4, 12)  # cap 0 at every bucket
    assert ops_cycles.cycles_max_dispatch(16) == 0
    got = ops_cycles.has_cycle_batch(mats)
    assert list(got) == [True, False, True, False]


def test_screen_plan_budget_and_cost_ranking():
    small = ops_cycles.ScreenPlan(16, (1, 3, 7), ((4, 3),))
    big = ops_cycles.ScreenPlan(64, (1, 3, 7), ((4, 3),))
    assert small.disp > big.disp > 0
    rows = [(None, i) for i in range(8)]
    pb_small = planning.PlannedBucket(None, small, None, rows)
    pb_big = planning.PlannedBucket(None, big, None, rows)
    assert planning.estimated_cost(pb_big) > planning.estimated_cost(
        pb_small
    )


def test_calibration_cost_table_serves_cycles_rows(tmp_path):
    """A calibration artifact with packed (kernel="cycles", E=n, C=0,
    F=plane-weight) rows drives estimated_cost for screen buckets —
    measured seconds, not the analytic proxy — and unmeasured shapes
    scale by the E²·F packed footprint."""
    from jepsen_tpu.tune import artifact

    data = artifact.build_artifact(
        {"window": 4, "flush_rows": 16384, "row_bucket": 64,
         "union_mode": "unroll", "closure_mode": "fixed",
         "closure_impl": "uint8"},
        [{"kernel": "cycles", "E": 16, "C": 0, "F": 7, "rows": 8,
          "seconds": 0.004},
         {"kernel": "cycles", "E": 16, "C": 0, "F": 7, "rows": 32,
          "seconds": 0.01}],
        "cpu", 1, created_at="2026-08-04T00:00:00+00:00",
    )
    cal = artifact.Calibration(data)
    assert cal.cost("cycles", 16, 0, 7, 8) == pytest.approx(0.004)
    assert cal.cost("cycles", 16, 0, 7, 20) == pytest.approx(
        0.004 + (0.01 - 0.004) * 12 / 24
    )
    # unmeasured vertex bucket scales the measured neighbor by the E²
    # proxy (the shared plane weight cancels)
    assert cal.cost("cycles", 32, 0, 7, 8) == pytest.approx(
        0.004 * (32 * 32) / (16 * 16)
    )
    # unmeasured plane weight scales linearly in F
    assert cal.cost("cycles", 16, 0, 14, 8) == pytest.approx(0.004 * 2)
    artifact.set_active(cal)
    try:
        plan = ops_cycles.ScreenPlan(16, (1, 3, 7), ((4, 3),))
        assert plan.frontier == 7  # 3 masks + 4 per lifted query
        pb = planning.PlannedBucket(None, plan, None,
                                    [(None, i) for i in range(8)])
        assert planning.estimated_cost(pb) == pytest.approx(0.004)
    finally:
        artifact.set_active(None)


def test_tune_cost_table_measures_cycles(tmp_path):
    """The offline sweep's cost table gains packed (kernel="cycles",
    E=n, C=0, F=plane-weight) rows with the budget guardrail
    applied."""
    from jepsen_tpu.tune import calibrate

    runner = calibrate._Runner()
    prof = dict(calibrate.PROFILES["smoke"])
    corpora = {}  # the cycles arm needs no history corpus
    params = {"window": 4, "flush_rows": 16384, "row_bucket": 64,
              "union_mode": "unroll", "closure_mode": "fixed",
              "closure_impl": "uint8"}
    entries = calibrate.measure_cost_table(runner, corpora, prof, params)
    cyc = [e for e in entries if e["kernel"] == "cycles"]
    assert cyc, entries
    assert all(e["C"] == 0 and e["F"] == 7 and e["seconds"] >= 0
               for e in cyc)


# ---------------------------------------------------------------------------
# packed plane closures: equality, dot_general count, early-exit
# ---------------------------------------------------------------------------


def _screen_variants(n, masks, nonadj, rel):
    """(packed, closure_mode) → (members, walks, rounds) over every
    lowering of the screen kernel."""
    out = {}
    for packed in (True, False):
        for cm in ("fixed", "earlyexit"):
            fn = ops_cycles._screen_fn_variant(n, masks, nonadj, packed,
                                               cm)
            m_, w_, r_ = fn(rel)
            out[(packed, cm)] = (np.asarray(m_), np.asarray(w_),
                                 np.asarray(r_))
    return out


def test_packed_screens_match_per_mask_and_numpy():
    """Plane-packed one-closure screens ≡ the historical per-mask
    kernels ≡ the numpy oracle, on op-soup graph buckets from BOTH
    workloads plus a synthetic all-bits profile covering the suffixed
    masks and both lifted walk queries — every lowering × both closure
    modes."""
    rng = random.Random(45130)
    encs = []
    for mode, prep in (("rw-register", elle.rw_register.prepare),
                       ("list-append", elle.list_append.prepare)):
        for i in range(10):
            h = _soup_history(rng, mode, rng.randrange(4, 14), 3,
                              corrupt=(i % 2 == 0))
            g = prep(h, {"workload": mode})[0]
            encs.append(elle_encode.encode_graph(g))
    buckets, order = elle_encode.bucket_graphs(encs)
    checked = 0
    for key in order:
        n, masks, nonadj = key
        rel = elle_encode.stack_rel([encs[i] for i in buckets[key]], n)
        want_m, want_w = ops_cycles._np_screen(rel, masks, nonadj)
        for var, (m_, w_, _r) in _screen_variants(
            n, masks, nonadj, rel
        ).items():
            assert np.array_equal(m_, want_m), (key, var)
            assert np.array_equal(w_, want_w), (key, var)
            checked += 1
    assert checked >= 8, order
    # the full suffixed ladder (all five relation bits, both lifted
    # queries) — op-soup graphs canonicalize PR bits away, so pin the
    # realtime/process family on synthetic all-bits batches
    masks, nonadj = (1, 3, 7, 25, 27, 31), ((4, 3), (4, 27))
    nprng = np.random.default_rng(45131)
    for n in (16, 32):
        rel = (nprng.integers(0, 32, size=(6, n, n))
               * (nprng.random((6, n, n)) < 0.08)).astype(np.uint8)
        want_m, want_w = ops_cycles._np_screen(rel, masks, nonadj)
        for var, (m_, w_, _r) in _screen_variants(
            n, masks, nonadj, rel
        ).items():
            assert np.array_equal(m_, want_m), (n, var)
            assert np.array_equal(w_, want_w), (n, var)


def test_packed_screen_jaxpr_dot_general_count():
    """The peak-FLOP pin: a 5-filter packed screen bucket lowers to at
    most log₂(n)+2 batched dot_generals (one fused closure over the
    plane stack), where the per-mask reference pays ~5·log₂(n)."""
    import math

    import jax

    from jepsen_tpu.lint.jaxpr_audit import count_dot_generals

    n = 32
    masks = (1, 3, 7, 25, 31)
    rel = np.zeros((4, n, n), np.uint8)
    rounds = math.ceil(math.log2(n))
    packed = count_dot_generals(
        jax.make_jaxpr(
            ops_cycles._screen_fn_variant(n, masks, (), True, "fixed")
        )(rel).jaxpr
    )
    assert packed <= rounds + 2, packed
    per_mask = count_dot_generals(
        jax.make_jaxpr(
            ops_cycles._screen_fn_variant(n, masks, (), False, "fixed")
        )(rel).jaxpr
    )
    assert per_mask >= len(masks) * rounds, per_mask


def test_earlyexit_closure_identical_across_diameters():
    """Early-exit ≡ fixed-round has-cycle flags over chain/ring
    diameters 1..n, with the early exit never running MORE rounds and
    strictly saving on short-diameter batches."""
    n = 16
    fixed_fn = ops_cycles._closure_fn(n, "fixed")
    early_fn = ops_cycles._closure_fn(n, "earlyexit")
    saved_somewhere = False
    for d in range(1, n + 1):
        adj = np.zeros((2, n, n), bool)
        for i in range(d):
            adj[0, i, (i + 1) % n] = True   # d=n closes into a ring
        for i in range(min(d, n - 1)):
            adj[1, i, i + 1] = True         # acyclic chain twin
        f_flags, f_rounds = fixed_fn(adj)
        e_flags, e_rounds = early_fn(adj)
        assert np.array_equal(np.asarray(f_flags), np.asarray(e_flags)), d
        assert int(np.asarray(e_rounds).max()) <= int(
            np.asarray(f_rounds).max()
        ), d
        if int(np.asarray(e_rounds).max()) < int(
            np.asarray(f_rounds).max()
        ):
            saved_somewhere = True
    assert saved_somewhere


def test_screen_settle_records_rounds_metrics():
    """The engine-routed screens surface per-dispatch closure-rounds
    evidence: the rounds counter and the saved-rounds counter (labelled
    by closure mode) plus the packed-plane occupancy gauge."""
    from jepsen_tpu import obs

    graphs = [_rw_chain(9, i % 2 == 0) for i in range(6)]
    encs = [elle_encode.encode_graph(g) for g in graphs]
    obs.enable(reset=True)
    try:
        res = ops_cycles.screen_graphs(encs)
        assert all(r is not None for r in res)
        reg = obs.registry()
        mode = ops_cycles.closure_mode()
        assert (reg.value("jepsen_cycles_closure_rounds_total",
                          mode=mode) or 0) > 0
        assert reg.value("jepsen_cycles_closure_rounds_saved_total",
                         mode=mode) is not None
        occ = reg.value("jepsen_cycles_packed_plane_occupancy")
        assert occ is not None and 0.0 < occ <= 1.0, occ
    finally:
        obs.enable(reset=True)


# ---------------------------------------------------------------------------
# screens: canonicalization + router calibration
# ---------------------------------------------------------------------------


def _rw_chain(n, cyc=False):
    g = Graph()
    for i in range(n - 1):
        g.add_edge(i, i + 1, "ww")
    if cyc:
        g.add_edge(n - 1, 0, "rw")
    else:
        g.add_vertex(n - 1)
    return g


def test_graph_screen_canonicalizes_absent_relations():
    g = _rw_chain(6, cyc=True)  # ww path closed by one rw edge
    enc = elle_encode.encode_graph(g)
    # present bits are ww|rw only: every ladder mask (including the
    # process/realtime-suffixed ones) canonicalizes onto them, so no
    # wr or lifted-PR closure is ever built for this graph
    assert enc.present == 5
    assert enc.masks == (1, 5)
    assert enc.nonadj == ((4, 1),)
    (res,) = ops_cycles.screen_graphs([enc])
    s = elle_cycles.GraphScreen(enc, res)
    full = s.members(elle_encode.ALL_MASK)
    assert full == set(range(6))
    # suffixed-ladder query (ww|PR) answers from the plain ww closure
    assert s.members(elle_encode.WW_BIT | elle_encode.PR_MASK) == \
        frozenset()
    # nonadjacent walks start at the vertex carrying the rw edge
    assert s.nonadj(elle_encode.RW_BIT,
                    elle_encode.WW_BIT | elle_encode.WR_BIT
                    | elle_encode.PR_MASK) == {5}
    # a graph with no rw edges answers every nonadjacent query empty
    g2 = _rw_chain(4, cyc=False)
    enc2 = elle_encode.encode_graph(g2)
    assert enc2.nonadj == ()
    (res2,) = ops_cycles.screen_graphs([enc2])
    s2 = elle_cycles.GraphScreen(enc2, res2)
    assert s2.nonadj(elle_encode.RW_BIT, 3) == frozenset()


def test_classify_graphs_auto_calibrates_and_pins_cpu_on_mismatch(
    monkeypatch,
):
    # 40 graphs, 20 of them cyclic: the router keys on the batch of
    # 20 nine-vertex SCCs, not on the graphs
    graphs = [_rw_chain(9, i % 2 == 0) for i in range(40)]
    expected = [elle_cycles.classify(g) for g in graphs]

    monkeypatch.setattr(elle_cycles, "_CLASSIFY_CHOICE", {})
    out = elle_cycles.classify_graphs(graphs)
    assert out == expected
    key = (elle_cycles._screen_bucket(9), elle_cycles._screen_bucket(20))
    assert elle_cycles._CLASSIFY_CHOICE.get(key) in ("cpu", "device")
    assert elle_cycles.classify_graphs(graphs) == expected

    # a lying screen pins the bucket to CPU, with the CPU answer kept
    monkeypatch.setattr(elle_cycles, "_CLASSIFY_CHOICE", {})
    monkeypatch.setattr(
        elle_cycles, "_classify_screened",
        lambda gs, executor=None, count=True, **_kw: [{} for _ in gs],
    )
    out = elle_cycles.classify_graphs(graphs)
    assert out == expected
    assert elle_cycles._CLASSIFY_CHOICE.get(key) == "cpu"

    # a crashing screen path raises instead of pinning the CPU
    def boom(gs, executor=None, count=True, **_kw):
        raise RuntimeError("no backend")

    monkeypatch.setattr(elle_cycles, "_CLASSIFY_CHOICE", {})
    monkeypatch.setattr(elle_cycles, "_classify_screened", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        elle_cycles.classify_graphs(graphs)
    assert elle_cycles._CLASSIFY_CHOICE == {}

    # small batches never calibrate under auto (stay on CPU)
    monkeypatch.setattr(elle_cycles, "_CLASSIFY_CHOICE", {})
    monkeypatch.setattr(elle_cycles, "_classify_screened", boom)
    assert elle_cycles.classify_graphs(graphs[:4]) == expected[:4]
    assert elle_cycles._CLASSIFY_CHOICE == {}


# ---------------------------------------------------------------------------
# partition-aware cost scheduling
# ---------------------------------------------------------------------------


def test_pipeline_finish_orders_buckets_globally_by_cost(monkeypatch):
    """End-of-input buckets dispatch largest-estimated-cost first
    ACROSS streams (pass-through + decomposed sub-histories), not
    merely within each stream."""
    from jepsen_tpu import models as m
    from jepsen_tpu.ops import wgl
    from jepsen_tpu.synth import generate_mr_history

    rng = random.Random(45100)
    # two length classes → sub-histories land in different (E, C)
    # buckets, so finish() has several buckets to order globally
    hists = [
        generate_mr_history(rng, n_procs=3, n_ops=n_ops, n_keys=4,
                            n_values=4, crash_p=0.0,
                            corrupt=(i % 3 == 0))
        for i, n_ops in enumerate([40, 40, 40, 220, 220, 220])
    ]
    model = m.multi_register({k: 0 for k in range(4)})

    seen = []
    orig = execution.Executor.submit

    def spy(self, pb):
        seen.append(planning.estimated_cost(pb))
        return orig(self, pb)

    monkeypatch.setattr(execution.Executor, "submit", spy)
    res = wgl.check_batch(model, hists, decomposed=True)
    assert all(r["valid?"] in (True, False) for r in res)
    assert len(seen) >= 2
    assert seen == sorted(seen, reverse=True), seen


def test_daemon_dispatches_groups_largest_cost_first(monkeypatch):
    """The daemon's largest-cost-first ordering now applies ACROSS
    compatible groups: a group's cost is the sum over its planned
    (post-decomposition) bucket rows, so high-fanout runs stop being
    under-scheduled by arrival order."""
    from jepsen_tpu import models as m
    from jepsen_tpu.engine import decompose
    from jepsen_tpu.ops import wgl
    from jepsen_tpu.serve import daemon as daemon_mod
    from jepsen_tpu.synth import generate_batch

    def make_req(model, hists, gkey):
        plan_opts = {"slot_cap": 32, "frontier": wgl.DEFAULT_FRONTIER,
                     "max_closure": None,
                     "max_dispatch": wgl.DEFAULT_MAX_DISPATCH}
        exec_opts = {"escalation": wgl.ESCALATION_FACTORS,
                     "sufficient_rung": True,
                     "max_dispatch": wgl.DEFAULT_MAX_DISPATCH}
        run = decompose.DecomposedRun(model, hists, oracle_fallback=True)
        streams = []
        for tag, sctx in run.streams():
            planner = planning.Planner(
                sctx.model, spec=sctx.spec, **plan_opts
            )
            buckets, order = planner.encode_buckets(sctx)
            streams.append(daemon_mod._Stream(
                tag, sctx.model, sctx.spec, buckets, order))
        return daemon_mod._Request(run, streams, gkey, model, plan_opts,
                                   exec_opts, len(hists))

    small = make_req(
        m.cas_register(0),
        generate_batch(seed=1, n_histories=2, n_procs=3, n_ops=8),
        "small",
    )
    big = make_req(
        m.cas_register(0),
        generate_batch(seed=2, n_histories=12, n_procs=3, n_ops=60),
        "big",
    )

    dispatched = []
    orig = daemon_mod.CheckerDaemon._dispatch_group

    def spy(self, executor, reqs, planned, n_buckets):
        dispatched.append(reqs[0].group_key)
        return orig(self, executor, reqs, planned, n_buckets)

    monkeypatch.setattr(daemon_mod.CheckerDaemon, "_dispatch_group", spy)
    d = daemon_mod.CheckerDaemon(port=0)
    ex = execution.Executor(None, mesh=None)
    d._process_batch(ex, [small, big])  # arrival order: small first
    assert dispatched == ["big", "small"]
    assert small.device_done.is_set() and big.device_done.is_set()
    small.run.drain_oracles()
    big.run.drain_oracles()
    assert all(r is not None for r in small.run.results())
    assert all(r is not None for r in big.run.results())


# ---------------------------------------------------------------------------
# the /elle service seam
# ---------------------------------------------------------------------------


def test_serve_elle_roundtrip_matches_in_process():
    from jepsen_tpu.serve import client as serve_client
    from jepsen_tpu.serve.daemon import CheckerDaemon

    graphs = [_rw_chain(7, i % 2 == 0) for i in range(10)]
    encs = [elle_encode.encode_graph(g) for g in graphs]
    local = ops_cycles.screen_graphs(encs)

    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = serve_client.ServiceClient(port=daemon.port)
        wire = client.screen_graphs(encs)
        assert len(wire) == len(local)
        for a, b in zip(local, wire):
            assert set(a.members) == set(b.members)
            for k in a.members:
                assert np.array_equal(a.members[k], b.members[k])
            for k in a.walks:
                assert np.array_equal(a.walks[k], b.walks[k])
        st = daemon.status()
        assert st["elle_requests"] == 1
        assert st["elle_graphs"] == len(encs)
    finally:
        daemon.stop()


def test_serve_screen_seam_requires_opt_in(monkeypatch):
    from jepsen_tpu.serve import client as serve_client

    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    encs = [elle_encode.encode_graph(_rw_chain(5, True))]
    assert serve_client.screen_graphs(encs) is None
