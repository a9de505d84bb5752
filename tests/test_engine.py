"""Pipelined checker engine tests (jepsen_tpu/engine/).

The contract under test: verdicts are a pure function of the
histories — NEVER of the dispatch window size, the shape bucketing,
chunk boundaries, or how oracle fallbacks interleave with device work.
window=1 must reproduce the historical serial dispatch-sync-dispatch
path exactly; window≥2 must actually overlap (pinned via the
in-flight-depth gauge).  Runs on the CPU backend; the same code path
runs on real TPU hardware unmodified.
"""

import os
import random

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu import obs
from jepsen_tpu.checker import linear
from jepsen_tpu.engine import DispatchWindow, pipeline, planning
from jepsen_tpu.history import History, invoke_op, ok_op
from jepsen_tpu.ops import encode, wgl
from jepsen_tpu.synth import generate_history as _gen


def h(*ops) -> History:
    hist = History(ops)
    for i, op in enumerate(hist):
        op.index = i
        op.time = i
    return hist


def wide_history(n=40) -> History:
    """n concurrently-open ops: exceeds every slot cap → oracle row."""
    w = History([invoke_op(p, "write", 1) for p in range(n)])
    return w.index_ops()


def mixed_corpus(seed=45100, wide=True):
    """Seeded histories spanning two event buckets and two concurrency
    buckets, with a corrupted minority, plus one unencodable row."""
    rng = random.Random(seed)
    hists = []
    for i in range(4):
        hists.append(
            _gen(rng, n_procs=3, n_ops=10, crash_p=0.02, corrupt=(i % 2 == 0))
        )
    for i in range(4):
        hists.append(
            _gen(rng, n_procs=3, n_ops=75, crash_p=0.01, corrupt=(i % 2 == 0))
        )
    for i in range(3):
        hists.append(_gen(rng, n_procs=7, n_ops=14, corrupt=(i == 0)))
    if wide:
        hists.append(wide_history())
    return hists


def sig(r: dict):
    """The verdict-relevant projection of one result dict (excludes
    fields like sampled configs whose ordering is representational)."""
    return (
        r.get("valid?"),
        r.get("engine"),
        r.get("failed-event"),
        r.get("error"),
    )


def oracle_verdicts(model, hists):
    """The CPU oracle's ``valid?`` for each history."""
    return [
        linear.analysis(model, h0, pure_fs=("read",))["valid?"]
        for h0 in hists
    ]


# ---------------------------------------------------------------------------
# determinism: window sizes, bucket splits, chunk boundaries
# ---------------------------------------------------------------------------


def test_window_and_bucketing_preserve_verdicts_dense_route():
    hists = mixed_corpus()
    model = m.cas_register(0)
    oracle = oracle_verdicts(model, hists)
    assert True in oracle and False in oracle  # corpus mixes verdicts
    for window in (1, 2, 4):
        outs = wgl.check_batch(model, hists, window=window)
        assert [o["valid?"] for o in outs] == oracle, window
        # device rows stay device rows, the wide row stays an oracle row
        assert outs[-1]["engine"] == "oracle-fallback"
        assert all(o["engine"] == "tpu" for o in outs[:-1])


def test_window_preserves_verdicts_frontier_route_across_chunks():
    """Explicit max_closure forces the generic frontier kernel; a tiny
    max_dispatch forces several padded chunks per bucket.  Verdicts and
    failure events must be identical at every window size."""
    hists = mixed_corpus(seed=7, wide=False)
    model = m.cas_register(0)
    base = wgl.check_batch(model, hists, max_closure=9, window=1)
    assert [o["valid?"] for o in base] == oracle_verdicts(model, hists)
    for window in (1, 4):
        outs = wgl.check_batch(
            model, hists, max_closure=9, max_dispatch=3, window=window,
        )
        assert [sig(o) for o in outs] == [sig(o) for o in base], window
        assert {o.get("kernel") for o in outs} == {"frontier"}


def test_escalation_interacts_with_pipelining():
    """Overflow rows must escalate (and resolve on-device) identically
    whether the base dispatches were pipelined or serial."""
    rng = random.Random(61)
    model = m.cas_register(0)
    hists = [
        _gen(rng, n_procs=5, n_ops=30, crash_p=0.02, corrupt=(i % 3 == 0))
        for i in range(9)
    ]
    base = wgl.check_batch(model, hists, window=1)
    assert [o["valid?"] for o in base] == oracle_verdicts(model, hists)
    esc = wgl.check_batch(
        model, hists, frontier=2, escalation=(4, 16), max_closure=7,
        slot_cap=6, max_dispatch=4, window=4,
    )
    assert [o["valid?"] for o in esc] == [o["valid?"] for o in base]


def test_tight_frontier_shapes_serialize_instead_of_overshooting():
    """When a frontier shape's safe dispatch cap is smaller than the
    window (per-row footprint near the whole crash-calibrated budget),
    the engine must dispatch that bucket strictly serially — windowed
    one-row dispatches would hold more concurrent footprint than the
    budget was measured for.  Verdicts must be unaffected."""
    rng = random.Random(31)
    model = m.cas_register(0)
    hists = [
        _gen(rng, n_procs=4, n_ops=16, crash_p=0.0, corrupt=(i % 2 == 0))
        for i in range(6)
    ]
    base = wgl.check_batch(model, hists, max_closure=8, window=1)
    old = wgl.FRONTIER_DISPATCH_BUDGET
    # E=64, C=4, F=128 → 1280 words/row: a 3000-word budget gives a
    # safe cap of 2 rows — below the window of 4
    wgl.FRONTIER_DISPATCH_BUDGET = 3000
    wgl.make_check_fn.cache_clear()  # cached fns carry stale caps
    obs.enable(reset=True)
    try:
        outs = wgl.check_batch(model, hists, max_closure=8, window=4)
    finally:
        wgl.FRONTIER_DISPATCH_BUDGET = old
        wgl.make_check_fn.cache_clear()
    assert [o["valid?"] for o in outs] == [o["valid?"] for o in base]
    # the frontier bucket never had two dispatches in flight
    assert obs.registry().value("jepsen_engine_inflight_depth") == 1
    obs.enable(reset=True)


def test_unknown_tags_without_oracle_fallback_are_window_invariant():
    """oracle_fallback=False (the race-mode contract): unresolved rows
    report the same unknown/engine tags at every window size."""
    hists = mixed_corpus(seed=3)
    model = m.cas_register(0)
    expected = None
    for window in (1, 4):
        outs = wgl.check_batch(
            model, hists, frontier=1, escalation=(), sufficient_rung=False,
            max_closure=1, oracle_fallback=False, window=window,
        )
        tags = [(o["valid?"], o["engine"]) for o in outs]
        assert tags[-1] == ("unknown", "unencodable")
        assert all(
            v == "unknown" and e == "overflow" for v, e in tags[:-1]
        ) or any(v is not None for v, _ in tags)  # overflow rows unknown
        if expected is None:
            expected = outs
        else:
            assert outs == expected, window


def test_oracle_deadline_abort_is_window_invariant():
    """The abort/deadline path: a zero oracle budget turns every
    fallback row into a deterministic budget-exceeded unknown, and the
    pipelined run must report it exactly like the serial one."""
    hists = mixed_corpus(seed=11)
    model = m.cas_register(0)
    runs = []
    for window in (1, 4):
        outs = wgl.check_batch(
            model, hists, frontier=1, escalation=(), sufficient_rung=False,
            max_closure=1, oracle_budget_s=0.0, window=window,
        )
        # device rows overflowed (frontier 1 + truncated closure) and the
        # oracle aborted on its budget: every verdict is an honest unknown
        assert all(o["valid?"] == "unknown" for o in outs if "budget"
                   in (o.get("error") or ""))
        runs.append([sig(o) for o in outs])
    assert runs[0] == runs[1]


def test_repeat_runs_identical_under_concurrent_oracle():
    """Oracle-pool interleaving must never leak into results: two
    identical pipelined runs produce identical result lists."""
    hists = mixed_corpus(seed=19)
    model = m.cas_register(0)
    a = wgl.check_batch(model, hists, window=4)
    b = wgl.check_batch(model, hists, window=4)
    assert [sig(o) for o in a] == [sig(o) for o in b]


# ---------------------------------------------------------------------------
# DispatchWindow mechanics
# ---------------------------------------------------------------------------


def test_dispatch_window_serializes_at_one_and_overlaps_above():
    events = []
    retired = []

    def mk(i):
        def thunk():
            events.append(("dispatch", i))
            return np.array([i])

        return thunk

    def on_retire(key, mat, _t):
        events.append(("retire", key))
        retired.append((key, int(mat[0])))

    win = DispatchWindow(1, on_retire=on_retire)
    for i in range(3):
        win.submit(i, mk(i))
    win.drain()
    # window=1 == the serial path: dispatch k+1 strictly after retire k
    assert events == [
        ("dispatch", 0), ("retire", 0),
        ("dispatch", 1), ("retire", 1),
        ("dispatch", 2), ("retire", 2),
    ]
    assert retired == [(0, 0), (1, 1), (2, 2)]
    assert win.peak_depth == 1

    events.clear()
    retired.clear()
    win = DispatchWindow(4, on_retire=on_retire)
    for i in range(3):
        win.submit(i, mk(i))
    # window not full: every dispatch issued before any sync
    assert events == [("dispatch", 0), ("dispatch", 1), ("dispatch", 2)]
    win.drain()
    assert retired == [(0, 0), (1, 1), (2, 2)]  # oldest-first
    assert win.peak_depth == 3


def test_dispatch_window_retires_oldest_when_full():
    order = []
    win = DispatchWindow(2, on_retire=lambda k, _m, _t: order.append(k))
    for i in range(5):
        win.submit(i, lambda i=i: np.array([i]))
    assert order == [0, 1, 2]  # forced out as the window refilled
    win.drain()
    assert order == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# bucketed encoding
# ---------------------------------------------------------------------------


def test_batch_encode_bucketed_tight_shapes_and_row_coverage():
    hists = mixed_corpus(wide=True)
    model = m.cas_register(0)
    single = encode.batch_encode(hists, model, slot_cap=32)
    buckets = encode.batch_encode(hists, model, slot_cap=32, bucketed=True)
    assert isinstance(buckets, list) and len(buckets) >= 2
    # every encodable history lands in exactly one bucket row
    covered = sorted(i for b in buckets for i in b.row_history)
    assert covered == sorted(single.row_history)
    # the global fallback list rides on the first bucket only
    assert buckets[0].fallback == single.fallback
    assert all(not b.fallback for b in buckets[1:])
    # shapes are tight: some bucket is strictly smaller than the global
    # padded shape in events or candidate lanes
    E_glob, C_glob = single.ev_slot.shape[1], single.cand_slot.shape[2]
    assert any(
        b.ev_slot.shape[1] < E_glob or b.cand_slot.shape[2] < C_glob
        for b in buckets
    )
    # bucket rows carry the same encoded data as the global stack
    # (modulo padding): compare each row's live event prefix
    pos = {idx: (bi, ri) for bi, b in enumerate(buckets)
           for ri, idx in enumerate(b.row_history)}
    for row, idx in enumerate(single.row_history):
        bi, ri = pos[idx]
        b = buckets[bi]
        E_b, C_b = b.ev_slot.shape[1], b.cand_slot.shape[2]
        np.testing.assert_array_equal(
            b.ev_slot[ri], single.ev_slot[row, :E_b]
        )
        np.testing.assert_array_equal(
            b.cand_slot[ri], single.cand_slot[row, :E_b, :C_b]
        )


def test_batch_encode_bucketed_all_fallback():
    model = m.cas_register(0)
    out = encode.batch_encode(
        [wide_history(), wide_history()], model, slot_cap=32, bucketed=True
    )
    assert len(out) == 1
    assert out[0].init_state.shape[0] == 0
    assert out[0].fallback == [0, 1]


def test_bucket_key_matches_single_batch_rounding():
    e = encode.encode_history(
        h(
            invoke_op(0, "write", 1),
            invoke_op(1, "read"),
            ok_op(0, "write", 1),
            ok_op(1, "read", 1),
        ),
        m.cas_register(None),
    )
    assert encode.bucket_key(e, slot_cap=32) == (64, 4)
    assert encode.bucket_key(e, slot_cap=2) == (64, 2)  # capped


# ---------------------------------------------------------------------------
# telemetry + satellite integrations
# ---------------------------------------------------------------------------


def test_pipeline_metrics_and_span():
    hists = mixed_corpus(wide=False)
    model = m.cas_register(0)
    obs.enable(reset=True)
    wgl.check_batch(model, hists, window=4, max_dispatch=3)
    reg = obs.registry()
    assert (reg.value("jepsen_engine_inflight_depth") or 0) > 1
    assert (reg.value("jepsen_engine_bucket_count") or 0) >= 2
    # the engine's streaming bucketer and batch_encode(bucketed=True)
    # share bucket_key/stack_encoded; this pins that they also AGREE on
    # the partition, so neither implementation can silently drift
    assert reg.value("jepsen_engine_bucket_count") == len(
        encode.batch_encode(hists, model, bucketed=True)
    )
    bubble = [
        d for d in reg.snapshot()
        if d["name"] == "jepsen_engine_bubble_seconds"
    ]
    assert bubble and bubble[0]["count"] > 0
    spans = obs.tracer().finished(cat="engine")
    (pipe,) = [s for s in spans if s.name == "engine/pipeline"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # one encode per chunk (here the whole batch), carrying its
    # histories' event count
    encs = by_name["engine/encode"]
    assert len(encs) == 1
    assert sum(int(s.attrs["histories"]) for s in encs) == len(hists)
    assert sum(int(s.attrs["events"]) for s in encs) == sum(
        len(x) for x in hists)
    # one plan per flush, one issue per dispatch, a settle per retire
    assert len(by_name["engine/plan"]) == int(pipe.attrs["flushes"])
    for s in by_name["engine/plan"]:
        assert {"rows", "E", "C", "kernel"} <= set(s.attrs)
    assert len(by_name["engine/issue"]) == int(pipe.attrs["chunks"]) > 1
    assert len(by_name["engine/settle"]) >= len(by_name["engine/dispatch"])
    assert by_name["engine/dispatch"]
    for name in ("engine/encode", "engine/plan", "engine/issue",
                 "engine/dispatch", "engine/settle"):
        for s in by_name[name]:
            assert s.parent == pipe.sid, name
            assert pipe.t0 <= s.t0 <= s.t1 <= pipe.t1
    # the summary surfaces the pipeline facts
    s = obs.summary()
    assert s.get("engine-inflight-depth", 0) > 1
    assert "engine-occupancy" not in s
    obs.enable(reset=True)


def test_keyed_check_records_one_split_span():
    from jepsen_tpu import independent
    from jepsen_tpu.history import Op

    rng = random.Random(7)
    history = History([
        Op(op.type, op.process, op.f, independent.kv(k, op.value))
        for k in range(5)
        for op in _gen(rng, n_procs=3, n_ops=8, crash_p=0.0)
    ]).index_ops()
    obs.enable(reset=True)
    res = independent.batched_linearizable(m.cas_register(0)).check(
        {"store?": False}, history)
    assert len(res["results"]) == 5
    (split,) = [s for s in obs.tracer().finished()
                if s.name == "checker/split"]
    assert split.cat == "checker"
    assert split.attrs["keys"] == "5"
    assert int(split.attrs["ops"]) == len(history.client_ops())
    # the split runs before the engine, outside its span
    (pipe,) = [s for s in obs.tracer().finished()
               if s.name == "engine/pipeline"]
    assert split.t1 <= pipe.t0
    obs.enable(reset=True)


def test_engine_spans_mirror_onto_the_profiler_trace(tmp_path):
    """With obs on, every engine span is also a profiler annotation of
    the same name on the host plane, nested as the spans nest."""
    import glob

    import jax
    from jax.profiler import ProfileData

    hists = mixed_corpus(wide=False)
    obs.enable(reset=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        wgl.check_batch(m.cas_register(0), hists, window=4,
                        max_dispatch=3)
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in obs.tracer().finished()
             if s.name.startswith("engine/")]
    obs.enable(reset=True)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine/"):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    by_name = {}
    for s in sorted(spans, key=lambda s: s.t0):
        by_name.setdefault(s.name, []).append(s)
    assert {"engine/pipeline", "engine/encode", "engine/plan",
            "engine/issue", "engine/dispatch",
            "engine/settle"} <= set(by_name)
    event_of = {}
    for name, ss in by_name.items():
        evs = sorted(host.get(name, []))
        assert len(evs) == len(ss), name
        for s, e in zip(ss, evs):
            event_of[s.sid] = e
    for s in spans:
        if s.parent in event_of:
            (ps, pe), (cs, ce) = event_of[s.parent], event_of[s.sid]
            assert ps <= cs <= ce <= pe, s.name


def test_window_one_records_serial_depth():
    hists = mixed_corpus(wide=False)
    model = m.cas_register(0)
    obs.enable(reset=True)
    wgl.check_batch(model, hists, window=1, max_dispatch=3)
    assert obs.registry().value("jepsen_engine_inflight_depth") == 1
    obs.enable(reset=True)


def test_per_chip_budget_accounting_under_mesh():
    """The acceptance hook: with a mesh of n devices and a window of
    W, no compiled fn's peak concurrently-in-flight PER-CHIP rows may
    exceed its single-chip cap (frontier chunks take n × disp/W rows
    globally = disp/W per chip; dense keeps the full per-chip cap ×
    window, the measured bench pattern) — asserted through the
    executor's chip_row_accounting."""
    import jax

    from jepsen_tpu.engine import execution, planning
    from jepsen_tpu.parallel import mesh as mesh_mod

    devs = jax.devices("cpu")
    assert len(devs) >= 8
    mesh = mesh_mod.default_mesh(devs[:8])
    model = m.cas_register(0)
    hists = mixed_corpus(seed=13, wide=False)
    # frontier route (max_closure), small max_dispatch so several
    # chunks are in flight at once
    ctx = planning.RunContext(model, hists)
    planner = planning.Planner(
        model, spec=ctx.spec, slot_cap=32, frontier=64, max_closure=9,
        max_dispatch=8, n_devices=8,
    )
    ex = pipeline.Executor(4, mesh=mesh, max_dispatch=8)
    for pb in planner.stream(ctx):
        ex.submit(pb)
    ex.drain()
    ctx.drain_oracles()
    assert ex.n_devices == 8
    accts = list(ex.chip_row_accounting.values())
    frontier_accts = [a for a in accts if a["kernel"] == "frontier"]
    assert frontier_accts, "no frontier dispatches recorded"
    for a in accts:
        cap = a["chip_cap"]
        if a["kernel"] == "dense":
            cap = cap * ex.window_size  # multi-in-flight dense is by design
        assert 0 < a["peak_chip_rows"] <= cap, a
    # in-flight accounting fully settles at drain
    assert all(v == 0 for v in ex._chip_rows_inflight.values())
    # verdicts unharmed by the accounting path
    assert [r["valid?"] for r in ctx.results] == [
        linear.analysis(model, h0, pure_fs=("read",))["valid?"]
        for h0 in hists
    ]


def test_executor_reset_clears_chip_accounting():
    from jepsen_tpu.engine import execution

    ex = execution.Executor(2, mesh=None)
    ex._chip_rows_inflight[123] = 7
    ex.reset()
    assert ex._chip_rows_inflight == {}


def test_analysis_async_matches_sync():
    model = m.cas_register(0)
    hist = mixed_corpus(wide=False)[0]
    fut = linear.analysis_async(model, hist, pure_fs=("read",))
    assert fut.result() == linear.analysis(model, hist, pure_fs=("read",))


def test_engine_window_env_default(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_ENGINE_WINDOW", "7")
    assert pipeline.default_window() == 7
    monkeypatch.setenv("JEPSEN_TPU_ENGINE_WINDOW", "junk")
    assert pipeline.default_window() == pipeline.DEFAULT_WINDOW


def test_cycles_screen_windowed_and_cache_bounded():
    from jepsen_tpu.ops import cycles as ops_cycles

    assert (
        ops_cycles._closure_fn.cache_info().maxsize
        == ops_cycles.CLOSURE_CACHE_SIZE
    )
    assert (
        ops_cycles._reach_fn.cache_info().maxsize
        == ops_cycles.CLOSURE_CACHE_SIZE
    )
    rng = np.random.default_rng(5)
    mats = []
    expected = []
    for n in (3, 10, 20, 40):
        a = np.zeros((n, n), dtype=bool)
        for i in range(n - 1):
            a[i, i + 1] = True
        cyclic = bool(rng.integers(0, 2))
        if cyclic:
            a[n - 1, 0] = True  # close the chain into a ring
        mats.append(a)
        expected.append(cyclic)
    for window in (None, 1, 3):
        got = ops_cycles.has_cycle_batch(mats, window=window)
        assert got.tolist() == expected, window


def test_cli_engine_window_validated_and_exported(monkeypatch):
    """--engine-window rejects values below serial (0 is NOT a disable
    switch) and exports the bound to JEPSEN_TPU_ENGINE_WINDOW so every
    DispatchWindow in the process (e.g. the Elle screen) honors it."""
    import argparse

    from jepsen_tpu import cli

    with pytest.raises(argparse.ArgumentTypeError):
        cli._engine_window_arg("0")
    with pytest.raises(argparse.ArgumentTypeError):
        cli._engine_window_arg("-2")
    assert cli._engine_window_arg("3") == 3

    monkeypatch.delenv("JEPSEN_TPU_ENGINE_WINDOW", raising=False)
    args = argparse.Namespace(
        nodes="n1", node=None, nodes_file=None, time_limit=1,
        store_base="store", leave_db_running=False, logging_json=False,
        username="root", password=None, ssh_private_key=None,
        concurrency=None, dummy=True, engine_window=2,
    )
    test = cli.test_opts_to_map(args)
    assert test["engine-window"] == 2
    # no process-wide leak from option mapping …
    assert "JEPSEN_TPU_ENGINE_WINDOW" not in os.environ
    # … run_test scopes the export to the run and restores afterwards
    from jepsen_tpu import core

    seen = {}

    def fake_run(t):
        seen["win"] = os.environ.get("JEPSEN_TPU_ENGINE_WINDOW")
        return {"results": {"valid?": True}}

    monkeypatch.setattr(core, "run", fake_run)
    assert cli.run_test(test) == cli.EXIT_VALID
    assert seen["win"] == "2"
    assert "JEPSEN_TPU_ENGINE_WINDOW" not in os.environ


def test_batched_linearizable_reads_engine_window():
    """The CLI's --engine-window lands in test['engine-window'] and
    flows through batched_linearizable into the engine."""
    from jepsen_tpu import independent as ind

    rng = random.Random(23)
    hists = {
        k: _gen(rng, n_procs=3, n_ops=8, crash_p=0.0) for k in ("a", "b")
    }
    history = History()
    for k, sub in hists.items():
        for op in sub:
            history.append(op.copy(value=ind.kv(k, op.value)))
    history.index_ops()
    chk = ind.batched_linearizable(m.cas_register(0))
    out = chk.check(
        {"engine-window": 2, "store?": False}, history, {}
    )
    assert out["valid?"] is True
    assert set(out["results"]) == {"a", "b"}


def test_bucket_stream_finish_orders_big_buckets_first():
    """End-of-input buckets dispatch largest-estimated-cost first
    (BucketStream.finish) — the per-run half of the daemon's
    largest-cost-first scheduling — with first-seen order preserved
    between equal-cost buckets."""
    model = m.cas_register(0)
    # 2 short rows land in a small bucket first, then 6 long rows in a
    # bigger-cost bucket: first-seen order is small-first, finish must
    # flip it
    rng = random.Random(7)
    hists = [_gen(rng, n_procs=3, n_ops=8, crash_p=0.0) for _ in range(2)]
    hists += [_gen(rng, n_procs=3, n_ops=75, crash_p=0.0) for _ in range(6)]
    ctx = planning.RunContext(model, hists)
    planner = planning.Planner(model, spec=ctx.spec, slot_cap=32,
                               frontier=64)
    stream = planner.open_stream()
    for idx in range(len(hists)):
        assert list(stream.feed(ctx, idx)) == []  # below flush_rows
    out = list(stream.finish())
    assert len(out) >= 2
    costs = [planning.estimated_cost(pb) for pb in out]
    assert costs == sorted(costs, reverse=True)
    assert costs[0] > costs[-1]


def test_planner_stream_equals_feed_finish_composition():
    """Planner.stream is exactly open_stream + feed* + finish: same
    buckets, same rows, same plans."""
    model = m.cas_register(0)
    hists = mixed_corpus(seed=3, wide=False)
    ctx_a = planning.RunContext(model, hists)
    planner_a = planning.Planner(model, spec=ctx_a.spec, slot_cap=32,
                                 frontier=64)
    via_stream = [
        (pb.key, len(pb.rows)) for pb in planner_a.stream(ctx_a)
    ]
    ctx_b = planning.RunContext(model, hists)
    planner_b = planning.Planner(model, spec=ctx_b.spec, slot_cap=32,
                                 frontier=64)
    s = planner_b.open_stream()
    via_feed = []
    for idx in range(len(hists)):
        via_feed.extend((pb.key, len(pb.rows)) for pb in s.feed(ctx_b, idx))
    via_feed.extend((pb.key, len(pb.rows)) for pb in s.finish())
    assert via_stream == via_feed
    assert planner_a.n_buckets == planner_b.n_buckets
    assert planner_a.n_flushes == planner_b.n_flushes


@pytest.mark.parametrize("model_name", ["cas-register", "mutex"])
def test_chunked_bucket_stream_matches_per_history_encode(monkeypatch,
                                                          model_name):
    """BucketStream encodes a chunk at a time: bucket keys, row tokens
    (in order) and oracle-routed rows equal one-history-at-a-time
    encode, settled rows are skipped, the engine/encode spans carry
    the encoded histories' events, and the path counter counts both
    the columnar pass and the per-history one.  A spec with no columnar
    encoder encodes each history as it is fed."""
    monkeypatch.setattr(encode, "CHUNK_EVENTS", 50)  # several chunks
    # chunks this small would not pay for the columnar pass: take it
    monkeypatch.setattr(encode, "_PASS_COST_EVENTS", 0)
    if model_name == "cas-register":
        model = m.cas_register(0)
        hists = mixed_corpus(seed=28)
        # a process invoking twice: the chunk's per-history path
        hists.insert(3, History([invoke_op(0, "write", 1),
                                 invoke_op(0, "write", 2),
                                 ok_op(0, "write", 2)]).index_ops())
    else:
        model = m.mutex()
        hists = [History([invoke_op(p, "acquire"), ok_op(p, "acquire"),
                          invoke_op(p, "release"), ok_op(p, "release")]
                         * k).index_ops()
                 for p, k in [(0, 1), (1, 3), (2, 20), (0, 2)]]
        hists.append(wide_history())
    settled = {1, len(hists) - 3}
    ctx = planning.RunContext(model, hists, oracle_fallback=False)
    for i in settled:
        ctx.assign(i, {"valid?": True, "engine": "replayed"})
    planner = planning.Planner(model, spec=ctx.spec, slot_cap=32,
                               frontier=64)
    obs.enable(reset=True)
    stream = planner.open_stream()
    got = []
    for idx in range(len(hists)):
        got.extend(stream.feed(ctx, idx))
    got.extend(stream.finish())
    spans = [s for s in obs.tracer().finished(cat="engine")
             if s.name == "engine/encode"]
    reg = obs.registry()
    columnar = reg.value("jepsen_engine_encoded_histories_total",
                         path="columnar") or 0
    per_history = reg.value("jepsen_engine_encoded_histories_total",
                            path="per_history") or 0
    obs.enable(reset=True)

    # one history at a time, as before chunking
    want_rows, want_routed, events = {}, [], 0
    for i, hist in enumerate(hists):
        if i in settled:
            continue
        e = encode.encode_history(hist, model, 32, ctx.spec)
        if e is None:
            want_routed.append(i)
            continue
        events += len(hist)
        want_rows.setdefault(encode.bucket_key(e, 32), []).append(i)
    assert {pb.key: [idx for _, idx in pb.rows] for pb in got} == want_rows
    assert all(c is ctx for pb in got for c, _ in pb.rows)
    assert [i for i, r in enumerate(ctx.results)
            if r and r["engine"] == "unencodable"] == want_routed
    assert want_routed  # the wide history
    assert len(spans) > 1
    assert sum(int(s.attrs["events"]) for s in spans) == events
    assert sum(int(s.attrs["histories"]) for s in spans) == (
        len(hists) - len(settled))
    assert columnar + per_history == len(hists) - len(settled)
    if model_name == "cas-register":
        assert per_history == 1 and columnar > 1
    else:
        assert columnar == 0
        assert len(spans) == len(hists) - len(settled)  # one a history
