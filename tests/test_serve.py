"""Checker-service tests (jepsen_tpu/serve/ + the engine's
planning/execution split).

The contract under test: verdicts are a pure function of the
histories — never of WHICH composition of the engine's two halves ran
them (the per-run pipeline vs the resident daemon's shared executor),
never of how many concurrent clients coalesced into a device batch,
and never of whether a daemon was reachable at all (the client seam
falls back in-process transparently).
"""

import random
import subprocess
import threading
import time

import pytest

from jepsen_tpu import models as m
from jepsen_tpu import obs
from jepsen_tpu.engine import (
    Executor,
    Planner,
    RunContext,
    merge_buckets,
    pipeline,
)
from jepsen_tpu.history import History, invoke_op
from jepsen_tpu.ops import wgl
from jepsen_tpu.serve import (
    CheckerDaemon,
    ServiceChecker,
    ServiceClient,
    ServiceError,
    UnsupportedModel,
    protocol,
)
from jepsen_tpu.serve import client as serve_client
from jepsen_tpu.synth import generate_history as _gen


def mixed_corpus(seed=45100, n=9, wide=True):
    rng = random.Random(seed)
    hists = []
    for i in range(n // 3):
        hists.append(_gen(rng, n_procs=3, n_ops=10, crash_p=0.02,
                          corrupt=(i % 2 == 0)))
    for i in range(n // 3):
        hists.append(_gen(rng, n_procs=3, n_ops=70, crash_p=0.01,
                          corrupt=(i % 2 == 0)))
    for i in range(n - 2 * (n // 3)):
        hists.append(_gen(rng, n_procs=7, n_ops=14, corrupt=(i == 0)))
    if wide:
        w = History([invoke_op(p, "write", 1) for p in range(40)])
        hists.append(w.index_ops())
    return hists


def sig(r):
    return (r.get("valid?"), r.get("engine"), r.get("failed-event"),
            r.get("error"))


# ---------------------------------------------------------------------------
# planning/execution split
# ---------------------------------------------------------------------------


def test_split_composition_matches_pipeline_run():
    """Hand-wiring Planner → Executor (the daemon's composition, minus
    HTTP) must produce exactly the verdicts engine.pipeline.run
    produces for the mixed-length smoke batch."""
    from jepsen_tpu.engine.smoke import _corpus

    hists = _corpus()
    model = m.cas_register(0)
    expected = pipeline.run(
        model, hists, frontier=wgl.DEFAULT_FRONTIER, slot_cap=32,
        max_dispatch=4,
    )

    ctx = RunContext(model, hists)
    planner = Planner(model, spec=ctx.spec, slot_cap=32,
                      frontier=wgl.DEFAULT_FRONTIER, max_dispatch=4)
    ex = Executor(max_dispatch=4)
    buckets, order = planner.encode_buckets(ctx)
    merged, morder = merge_buckets([(buckets, order)])
    for key in morder:
        pb = planner.plan_rows(key, *merged[key])
        if pb is not None:
            ex.submit(pb)
    ex.drain()
    ctx.drain_oracles()
    assert [sig(r) for r in ctx.results] == [sig(r) for r in expected]


def test_merge_buckets_coalesces_across_contexts_and_routes_rows():
    """Two contexts' same-shape buckets merge into shared stacks whose
    row tokens still point at the right (ctx, idx) — verdicts land in
    each context's own result slots."""
    model = m.cas_register(0)
    h_a = mixed_corpus(seed=3, n=6, wide=False)
    h_b = mixed_corpus(seed=11, n=6, wide=False)
    exp_a = wgl.check_batch(model, h_a, slot_cap=32)
    exp_b = wgl.check_batch(model, h_b, slot_cap=32)

    ctx_a = RunContext(model, h_a)
    ctx_b = RunContext(model, h_b)
    planner = Planner(model, spec=ctx_a.spec, slot_cap=32,
                      frontier=wgl.DEFAULT_FRONTIER)
    runs = [planner.encode_buckets(ctx_a), planner.encode_buckets(ctx_b)]
    merged, order = merge_buckets(runs)
    # same seeds shapes overlap: at least one merged bucket holds rows
    # from BOTH contexts (the coalescing the service exists for)
    assert any(
        {id(t[0]) for t in merged[k][1]} == {id(ctx_a), id(ctx_b)}
        for k in order
    )
    ex = Executor()
    for key in order:
        pb = planner.plan_rows(key, *merged[key])
        if pb is not None:
            ex.submit(pb)
    ex.drain()
    ctx_a.drain_oracles()
    ctx_b.drain_oracles()
    assert [sig(r) for r in ctx_a.results] == [sig(r) for r in exp_a]
    assert [sig(r) for r in ctx_b.results] == [sig(r) for r in exp_b]


def test_executor_reset_discards_transient_state():
    """The daemon's failure recovery: reset() must abandon in-flight
    dispatches (no sync — retiring could re-raise the device failure)
    and drop parked escalations, leaving the executor reusable."""
    import numpy as np

    from jepsen_tpu.engine.execution import DispatchWindow

    win = DispatchWindow(4)
    win.submit(0, lambda: np.array([0]))
    win.submit(1, lambda: np.array([1]))
    assert win.depth == 2
    assert win.abandon() == 2
    assert win.depth == 0

    ex = Executor(4)
    ex._pending_escalations.append(("poison",))
    ex._chunks[7] = {"poison": True}
    ex._win.submit(0, lambda: np.array([0]))
    assert ex.reset() == 1
    assert not ex._pending_escalations and not ex._chunks
    # still usable after reset: a real bucket round-trips
    model = m.cas_register(0)
    hists = mixed_corpus(seed=21, n=3, wide=False)
    ctx = RunContext(model, hists)
    planner = Planner(model, spec=ctx.spec, slot_cap=32,
                      frontier=wgl.DEFAULT_FRONTIER)
    buckets, order = planner.encode_buckets(ctx)
    for k in order:
        pb = planner.plan_rows(k, *buckets[k])
        if pb is not None:
            ex.submit(pb)
    ex.drain()
    ctx.drain_oracles()
    assert [sig(r) for r in ctx.results] == [
        sig(r) for r in wgl.check_batch(model, hists, slot_cap=32)
    ]


def test_estimated_cost_hook_orders_kernel_families():
    """The daemon's bucket-scheduling seam: oracle-routed buckets cost
    the device nothing, frontier rows dominate dense rows at equal
    shape, and cost grows with rows — the invariants a learned cost
    model must also satisfy to slot in."""
    from jepsen_tpu.engine import estimated_cost

    model = m.cas_register(0)
    hists = mixed_corpus(seed=7, n=6, wide=False)
    ctx = RunContext(model, hists)
    planner = Planner(model, spec=ctx.spec, slot_cap=32,
                      frontier=wgl.DEFAULT_FRONTIER)
    buckets, order = planner.encode_buckets(ctx)
    pbs = [planner.plan_rows(k, *buckets[k]) for k in order]
    assert all(estimated_cost(pb) > 0 for pb in pbs)
    # frontier planning of the same rows costs more than dense
    planner_f = Planner(model, spec=ctx.spec, slot_cap=32,
                        frontier=wgl.DEFAULT_FRONTIER, max_closure=9)
    ctx2 = RunContext(model, hists)
    b2, o2 = planner_f.encode_buckets(ctx2)
    for k in order:
        if k in b2:
            dense_pb = planner.plan_rows(k, *buckets[k])
            front_pb = planner_f.plan_rows(k, *b2[k])
            if dense_pb.plan.kernel == "dense":
                assert estimated_cost(front_pb) > estimated_cost(dense_pb)


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------


def test_model_wire_round_trip():
    cases = [
        m.register(None),
        m.register(3),
        m.cas_register(0),
        m.mutex(),
        m.multi_register({"x": 1, "y": 2}),
        # int-keyed registers are the synth/workload norm; a plain
        # JSON object would silently stringify the keys into a
        # DIFFERENT model (wrong verdicts) — the kv-pair wire form
        # must survive the full codec round trip
        m.multi_register({0: 0, 1: 0}),
        m.FIFOQueue((1, 2)),
        m.UnorderedQueue(frozenset({1, 2})),
    ]
    for model in cases:
        wire = protocol.decode_body(
            protocol.encode_body(protocol.model_to_wire(model)))
        back = protocol.model_from_wire(wire)
        assert type(back) is type(model)
        assert back == model, model


def test_multi_register_int_keys_verdict_parity_via_service():
    """The review repro: an int-keyed multi-register batch through the
    daemon must verdict exactly like the in-process engine (JSON-object
    keys would have flipped valid histories to invalid)."""
    from jepsen_tpu.history import ok_op

    model = m.multi_register({0: 0, 1: 0})
    good = History([
        invoke_op(0, "txn", [("w", 0, 5)]), ok_op(0, "txn", [("w", 0, 5)]),
        invoke_op(0, "txn", [("r", 0, None)]), ok_op(0, "txn", [("r", 0, 5)]),
        invoke_op(0, "txn", [("r", 1, None)]), ok_op(0, "txn", [("r", 1, 0)]),
    ]).index_ops()
    bad = History([
        invoke_op(0, "txn", [("w", 0, 5)]), ok_op(0, "txn", [("w", 0, 5)]),
        invoke_op(0, "txn", [("r", 1, None)]),
        ok_op(0, "txn", [("r", 1, 5)]),  # key 1 was never written
    ]).index_ops()
    expected = wgl.check_batch(model, [good, bad], slot_cap=8)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        got = ServiceClient(port=daemon.port).check_batch(
            model, [good, bad], slot_cap=8)
        assert [sig(r) for r in got] == [sig(r) for r in expected]
        assert got[0]["valid?"] is True and got[1]["valid?"] is False
    finally:
        daemon.stop()


def test_unsupported_model_raises():
    class Weird(m.Model):
        def step(self, op):
            return self

    with pytest.raises(UnsupportedModel):
        protocol.model_to_wire(Weird())
    with pytest.raises(UnsupportedModel):
        protocol.check_request(m.cas_register(0), [], {"window": 4})


def test_history_wire_round_trip_preserves_encoding():
    hists = mixed_corpus(seed=7, n=6, wide=False)
    model = m.cas_register(0)
    wire = protocol.histories_to_wire(hists)
    back = protocol.histories_from_wire(
        protocol.decode_body(protocol.encode_body(wire)))
    assert [sig(r) for r in wgl.check_batch(model, back, slot_cap=32)] == [
        sig(r) for r in wgl.check_batch(model, hists, slot_cap=32)
    ]


# ---------------------------------------------------------------------------
# the daemon
# ---------------------------------------------------------------------------


def test_daemon_concurrent_clients_coalesce_with_per_client_routing():
    model = m.cas_register(0)
    h_a = mixed_corpus(seed=3, n=6, wide=True)
    h_b = mixed_corpus(seed=11, n=6, wide=False)
    exp_a = wgl.check_batch(model, h_a, slot_cap=32)
    exp_b = wgl.check_batch(model, h_b, slot_cap=32)

    daemon = CheckerDaemon(port=0, coalesce_wait_s=0.6)
    daemon.start(block=False)
    try:
        out = {}
        barrier = threading.Barrier(2)

        def post(tag, hists):
            c = ServiceClient(port=daemon.port)
            barrier.wait()
            out[tag] = c.check_batch(model, hists, slot_cap=32)

        threads = [
            threading.Thread(target=post, args=("a", h_a)),
            threading.Thread(target=post, args=("b", h_b)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = daemon.status()
        assert st["coalesced"] >= 2  # one shared device batch
        assert [sig(r) for r in out["a"]] == [sig(r) for r in exp_a]
        assert [sig(r) for r in out["b"]] == [sig(r) for r in exp_b]
        # the unencodable wide history rode the daemon's oracle pool
        assert out["a"][-1]["engine"] == "oracle-fallback"
    finally:
        daemon.stop()


def test_daemon_backpressure_rejects_past_admission_bound():
    model = m.cas_register(0)
    hists = mixed_corpus(seed=5, n=3, wide=False)
    daemon = CheckerDaemon(port=0, max_queue_runs=1, coalesce_wait_s=2.0)
    daemon.start(block=False)
    try:
        ok = {}
        errs = []
        barrier = threading.Barrier(3)

        def post(tag):
            c = ServiceClient(port=daemon.port)
            barrier.wait()
            try:
                ok[tag] = c.check_batch(model, hists, slot_cap=32)
            except ServiceError as e:
                errs.append((tag, str(e)))

        threads = [threading.Thread(target=post, args=(t,))
                   for t in ("a", "b", "c")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # bound is 1 queued run: at least one concurrent client was
        # told to back off (503 → ServiceError → client-side fallback)
        assert errs and all("backlogged" in e for _, e in errs)
        assert ok  # and at least one was served
        expected = wgl.check_batch(model, hists, slot_cap=32)
        for res in ok.values():
            assert [sig(r) for r in res] == [sig(r) for r in expected]
        assert daemon.status()["rejected"] >= 1
    finally:
        daemon.stop()


def test_daemon_clean_shutdown_drains_in_flight_work():
    model = m.cas_register(0)
    hists = mixed_corpus(seed=9, n=6, wide=False)
    expected = wgl.check_batch(model, hists, slot_cap=32)
    daemon = CheckerDaemon(port=0, coalesce_wait_s=1.0)
    daemon.start(block=False)
    out = {}
    try:
        def post():
            c = ServiceClient(port=daemon.port)
            out["res"] = c.check_batch(model, hists, slot_cap=32)

        t = threading.Thread(target=post)
        t.start()
        import time as _time

        _time.sleep(0.2)  # admitted; device thread in its gather window
        ServiceClient(port=daemon.port).shutdown()
        t.join(timeout=30)
        assert [sig(r) for r in out.get("res") or []] == [
            sig(r) for r in expected
        ]
        # drained daemon stops admitting
        c2 = ServiceClient(port=daemon.port)
        deadline = _time.monotonic() + 10
        while c2.healthy(timeout=0.3) and _time.monotonic() < deadline:
            _time.sleep(0.1)
        assert not c2.healthy(timeout=0.3)
    finally:
        daemon.stop()


# ---------------------------------------------------------------------------
# client fallback + the check(...) seam
# ---------------------------------------------------------------------------


def _invalid_history():
    """A history the CPU oracle definitely rejects (corrupt=True only
    biases toward invalidity at small sizes)."""
    from jepsen_tpu.checker import linear

    rng = random.Random(2)
    for _ in range(64):
        h = _gen(rng, n_procs=3, n_ops=12, corrupt=True)
        if linear.analysis(
            m.cas_register(0), h, pure_fs=("read",)
        )["valid?"] is False:
            return h
    raise AssertionError("no invalid history found")


def _valid_history():
    from jepsen_tpu.checker import linear

    rng = random.Random(1)
    for _ in range(64):
        h = _gen(rng, n_procs=3, n_ops=12, corrupt=False)
        if linear.analysis(
            m.cas_register(0), h, pure_fs=("read",)
        )["valid?"] is True:
            return h
    raise AssertionError("no valid history found")


def _dead_port_client():
    """A client aimed at a port nothing listens on."""
    from jepsen_tpu.util import free_port

    return ServiceClient(port=free_port())


def test_client_falls_back_in_process_when_no_daemon(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    client = _dead_port_client()
    assert not client.healthy()
    model = m.cas_register(0)
    hists = mixed_corpus(seed=13, n=6, wide=False)
    expected = wgl.check_batch(model, hists, slot_cap=32)
    got = serve_client.check_batch(
        model, hists, client=client, slot_cap=32)
    assert [sig(r) for r in got] == [sig(r) for r in expected]


def test_service_checker_seam_without_daemon(monkeypatch):
    """ServiceChecker behind check(test, history, opts): no daemon
    listening → transparent in-process verdicts, both polarities."""
    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    monkeypatch.setenv("JEPSEN_TPU_SERVE_PORT", str(_dead_port_client().port))
    chk = ServiceChecker(m.cas_register(0))
    assert chk.check({}, _valid_history(), {})["valid?"] is True
    assert chk.check({}, _invalid_history(), {})["valid?"] is False


def test_service_checker_against_live_daemon(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        monkeypatch.setenv("JEPSEN_TPU_SERVE_PORT", str(daemon.port))
        chk = ServiceChecker(m.cas_register(0))
        assert chk.check({}, _valid_history(), {})["valid?"] is True
        out = chk.check({}, _invalid_history(), {})
        assert out["valid?"] is False
        assert daemon.status()["requests"] >= 2  # it really went over HTTP
    finally:
        daemon.stop()


def test_auto_algorithm_resolves_to_service_only_when_opted_in(monkeypatch):
    from jepsen_tpu import checker as checker_mod

    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    assert serve_client.service_mode() == "off"
    monkeypatch.setenv("JEPSEN_TPU_SERVICE", "1")
    assert serve_client.service_mode() == "on"
    monkeypatch.setenv("JEPSEN_TPU_SERVICE", "auto")
    assert serve_client.service_mode() == "auto"
    # opted in but nothing listening: "auto" checker still verdicts
    # correctly via the fallback chain
    monkeypatch.setenv("JEPSEN_TPU_SERVICE", "1")
    monkeypatch.setenv("JEPSEN_TPU_SERVE_PORT", str(_dead_port_client().port))
    chk = checker_mod.linearizable(m.cas_register(0))
    assert chk.check({}, _invalid_history(), {})["valid?"] is False


# ---------------------------------------------------------------------------
# render_prom (the shared formatter satellite)
# ---------------------------------------------------------------------------


def test_status_advertises_mesh_and_mesh_matched_requests_serviced(
    monkeypatch,
):
    """/status must advertise the resident mesh (n_devices +
    mesh_shape), and an explicit client mesh whose SHAPE matches it is
    serviceable (the PR-6 unserviceable-mesh restriction, lifted): the
    opt is dropped and the daemon's own identically-shaped mesh
    shards the batch.  A mismatched shape still runs in-process."""
    import jax

    from jepsen_tpu.parallel import mesh as mesh_mod

    monkeypatch.setenv("JEPSEN_TPU_ENGINE_MESH", "1")
    model = m.cas_register(0)
    hists = mixed_corpus(seed=21, n=6, wide=False)
    expected = wgl.check_batch(model, hists, slot_cap=32)

    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        st = daemon.status()
        assert st["n_devices"] == 8
        assert st["mesh_shape"] == [8]
        client = ServiceClient(port=daemon.port)

        devs = jax.devices("cpu")
        mesh8 = mesh_mod.default_mesh(devs[:8])
        out = serve_client.check_batch(
            model, hists, client=client, mesh=mesh8, slot_cap=32
        )
        assert [sig(r) for r in out] == [sig(r) for r in expected]
        served = daemon.status()["requests"]
        assert served == 1  # the mesh-matched batch went to the daemon

        mesh4 = mesh_mod.default_mesh(devs[:4])
        out4 = serve_client.check_batch(
            model, hists, client=client, mesh=mesh4, slot_cap=32
        )
        assert [r["valid?"] for r in out4] == [r["valid?"] for r in expected]
        # shape mismatch: honored in-process, daemon saw no new request
        assert daemon.status()["requests"] == served
    finally:
        daemon.stop()


# ---------------------------------------------------------------------------
# cross-seam trace propagation (fleet telemetry)
# ---------------------------------------------------------------------------


def test_trace_ctx_wire_round_trip():
    """trace_ctx rides the /check and /elle bodies verbatim and
    survives the codec; malformed contexts degrade to None (untraced),
    never to an error — telemetry must not fail a checker run."""
    from jepsen_tpu.obs import propagate

    ctx = propagate.make_ctx(parent_sid=7)
    assert propagate.parse_ctx(ctx) == ctx
    body = protocol.decode_body(protocol.check_request(
        m.cas_register(0), mixed_corpus(seed=3, n=3, wide=False)[:1],
        {}, trace_ctx=ctx))
    assert propagate.parse_ctx(body["trace_ctx"]) == ctx
    # absent by default: untraced runs send the pre-telemetry body
    body = protocol.decode_body(protocol.check_request(
        m.cas_register(0), [], {}))
    assert "trace_ctx" not in body
    for bad in (None, "x", 7, {}, {"trace_id": "UPPER", "parent_sid": 0},
                {"trace_id": "ab", "parent_sid": "zero"},
                {"trace_id": "g" * 8, "parent_sid": 1},
                {"trace_id": "a" * 65, "parent_sid": 1}):
        assert propagate.parse_ctx(bad) is None


def test_service_run_exports_one_stitched_trace():
    """A service-routed run is ONE trace: the client-side span and the
    daemon-side spans share a trace id, /trace?ctx= serves the
    daemon's dump for it, and the Chrome export stitches both sides
    with flow events."""
    import os as _os

    from jepsen_tpu.obs import export as obs_export
    from jepsen_tpu.obs import propagate

    obs.enable(reset=True)
    model = m.cas_register(0)
    hists = mixed_corpus(seed=17, n=3, wide=False)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = ServiceClient(port=daemon.port)
        client.check_batch(model, hists, slot_cap=32)

        spans = obs.tracer().finished()
        by_role = {}
        for s in spans:
            role = (s.attrs or {}).get(propagate.ATTR_ROLE)
            if role:
                by_role.setdefault(role, []).append(s)
        assert by_role.get("client") and by_role.get("daemon")
        tid = by_role["client"][0].attrs[propagate.ATTR_TRACE_ID]
        assert any(
            s.attrs[propagate.ATTR_TRACE_ID] == tid
            for s in by_role["daemon"]
        )
        # the daemon span is parented under the client's span id
        client_sid = by_role["client"][0].sid
        assert any(
            int(s.attrs.get("parent_sid", -1)) == client_sid
            for s in by_role["daemon"]
        )

        # /trace serves exactly this trace's daemon-side dump
        code, body = client._request(f"/trace?ctx={tid}")
        assert code == 200
        dump = protocol.decode_body(body)
        assert dump["spans"] and all(
            propagate.span_matches(s, tid) for s in dump["spans"])
        assert dump["pid"] == _os.getpid()

        # in-process daemon: adopt() must refuse same-pid dumps (the
        # spans are already in the shared tracer — adopting would
        # duplicate every event)
        assert propagate.adopt(
            dump["spans"], pid=dump["pid"],
            wall_origin=dump["wall_origin"],
            origin_ns=dump["origin_ns"]) == 0

        events = obs_export.chrome_trace(obs.tracer())["traceEvents"]
        flows = [e for e in events if e.get("cat") == "trace_ctx"
                 and e.get("id") == tid]
        assert {"s", "f"} <= {e["ph"] for e in flows}
    finally:
        daemon.stop()
        obs.enable(reset=True)


def test_adopted_remote_spans_merge_into_chrome_trace():
    """A genuinely remote dump (different pid) is adopted and rebased
    onto the local wall clock in the merged export."""
    import os as _os
    import time as _time

    from jepsen_tpu.obs import export as obs_export
    from jepsen_tpu.obs import propagate

    obs.enable(reset=True)
    t = obs.tracer()
    now = _time.monotonic_ns()
    remote = {
        "name": "serve/check", "cat": "serve", "t0": now,
        "t1": now + 5_000_000, "tid": 1, "pid": _os.getpid() + 1,
        "sid": 0, "parent": None,
        "attrs": {"trace_id": "ab12", "ctx_role": "daemon"},
    }
    assert propagate.adopt(
        [remote], pid=remote["pid"], wall_origin=t.wall_origin,
        origin_ns=now) == 1
    events = obs_export.chrome_trace(t)["traceEvents"]
    merged = [e for e in events if e.get("pid") == remote["pid"]]
    assert merged and merged[0]["name"] == "serve/check"
    assert abs(merged[0]["dur"] - 5_000.0) < 1.0  # µs
    obs.enable(reset=True)


def test_daemon_queue_wait_and_live_status():
    """Admission→dispatch queue wait is measured (the invisibility
    fix) and /status carries the last-60 s live view."""
    obs.enable(reset=True)
    model = m.cas_register(0)
    hists = mixed_corpus(seed=23, n=3, wide=False)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = ServiceClient(port=daemon.port)
        client.check_batch(model, hists, slot_cap=32)
        snap = {d["name"]: d for d in obs.registry().snapshot()}
        qw = snap.get("jepsen_serve_queue_wait_seconds")
        assert qw is not None and qw["count"] >= 1
        live = client.status()["live"]
        assert live["requests_per_s"] > 0
        assert live["queue_wait_mean_s"] is not None
        assert 0.0 <= live["device_busy_ratio"] <= 1.0
    finally:
        daemon.stop()
        obs.enable(reset=True)


def test_render_prom_matches_file_dump(tmp_path):
    from jepsen_tpu.obs import export as obs_export

    obs.enable(reset=True)
    obs.count("jepsen_serve_requests_total", 3)
    obs.observe("jepsen_oracle_seconds", 0.01)
    text = obs.render_prom()
    path = tmp_path / "metrics.prom"
    obs_export.write_prometheus(obs.registry(), str(path))
    assert path.read_text() == text
    assert obs_export.validate_prometheus_text(text) is None
    assert "jepsen_serve_requests_total 3" in text
    obs.enable(reset=True)


# ---------------------------------------------------------------------------
# client resilience: retry / deadline / circuit breaker (the nemesis
# turned on the checker — doc/checker-service.md "Failure modes &
# recovery"; the full kill/stall/drop matrix lives in serve/chaos.py)
# ---------------------------------------------------------------------------


def test_circuit_breaker_state_machine():
    br = serve_client.CircuitBreaker(failures=2, cooldown_s=0.05)
    assert br.state() == "closed" and br.allow()
    assert br.record_failure() is False
    assert br.state() == "closed"  # one short of the trip
    assert br.record_failure() is True  # this one trips it open
    assert br.state() == "open" and br.trips == 1
    # open within the cooldown: fast-fail, the probe is never run
    assert br.allow(lambda: 1 / 0) is False
    time.sleep(0.06)
    assert br.state() == "half-open"
    # half-open probe fails: re-opens for another cooldown
    assert br.allow(lambda: False) is False
    assert br.state() == "open" and br.probes == 1
    time.sleep(0.06)
    # half-open probe succeeds: closes and clears the failure count
    assert br.allow(lambda: True) is True
    assert br.state() == "closed" and br.probes == 2
    # a success between failures resets the consecutive count
    assert br.record_failure() is False
    br.record_success()
    assert br.record_failure() is False
    assert br.state() == "closed"


def test_breaker_trips_to_in_process_and_fast_fails(monkeypatch):
    """Consecutive connection failures trip the shared per-address
    breaker; while open, posts fast-fail without touching the socket,
    and the transparent seam above it still answers in-process."""
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_FAILURES", "2")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_COOLDOWN", "60")
    monkeypatch.setenv("JEPSEN_TPU_CLIENT_RETRIES", "0")
    monkeypatch.delenv("JEPSEN_TPU_SERVICE", raising=False)
    serve_client.reset_breakers()
    client = _dead_port_client()
    model = m.cas_register(0)
    hists = mixed_corpus(seed=17, n=3, wide=False)
    body = protocol.check_request(model, hists, {"slot_cap": 32})
    try:
        for _ in range(2):
            with pytest.raises(serve_client.ServiceUnavailable):
                client._resilient_post("/check", body)
        br = serve_client.breaker_for(client.host, client.port)
        assert br.state() == "open" and br.trips == 1
        with pytest.raises(serve_client.ServiceUnavailable,
                           match="circuit open"):
            client._resilient_post("/check", body)
        # the seam above the breaker: verdicts still arrive in-process
        got = serve_client.check_batch(model, hists, client=client,
                                       slot_cap=32)
        expected = wgl.check_batch(model, hists, slot_cap=32)
        assert [sig(r) for r in got] == [sig(r) for r in expected]
    finally:
        serve_client.reset_breakers()


def test_breaker_half_open_probe_recovers_against_live_daemon(
        monkeypatch):
    """After the cooldown a tripped breaker goes half-open: the next
    post runs one /healthz probe, which closes the breaker and lets
    the request through to a recovered daemon."""
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_FAILURES", "1")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_COOLDOWN", "0.2")
    monkeypatch.setenv("JEPSEN_TPU_CLIENT_RETRIES", "0")
    serve_client.reset_breakers()
    model = m.cas_register(0)
    hists = mixed_corpus(seed=19, n=3, wide=False)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = ServiceClient(port=daemon.port)
        br = serve_client.breaker_for(client.host, client.port)
        assert br.record_failure() is True and br.state() == "open"
        body = protocol.check_request(model, hists, {"slot_cap": 32})
        with pytest.raises(serve_client.ServiceUnavailable,
                           match="circuit open"):
            client._resilient_post("/check", body)
        time.sleep(0.25)
        assert br.state() == "half-open"
        code, resp = client._resilient_post("/check", body)
        assert code == 200
        assert br.state() == "closed" and br.probes == 1
        assert "results" in protocol.decode_body(resp)
    finally:
        daemon.stop()
        serve_client.reset_breakers()


def test_client_deadline_budget_is_a_hard_bound(monkeypatch):
    """The whole resilient post — attempts plus backoff sleeps — is
    bounded by JEPSEN_TPU_CLIENT_DEADLINE, and exhaustion is counted
    in the caller's registry."""
    monkeypatch.setenv("JEPSEN_TPU_CLIENT_DEADLINE", "1e-9")
    serve_client.reset_breakers()
    obs.enable(reset=True)
    client = _dead_port_client()
    t0 = time.monotonic()
    with pytest.raises(serve_client.ServiceUnavailable,
                       match="deadline budget"):
        client._resilient_post("/check", b"{}")
    assert time.monotonic() - t0 < 5.0
    assert "jepsen_client_deadline_exhausted_total" in obs.render_prom()
    obs.enable(reset=True)
    serve_client.reset_breakers()


def test_request_id_dedup_answers_retry_from_cache():
    """A retried POST /check carrying the same idempotent request id
    is answered from the completed-response cache: byte-identical
    payload, and the work is never admitted (or counted) twice."""
    model = m.cas_register(0)
    hists = mixed_corpus(seed=29, n=3, wide=False)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = ServiceClient(port=daemon.port)
        body = protocol.check_request(
            model, hists, {"slot_cap": 32}, req="retry-dup-1")
        code1, resp1 = client._resilient_post("/check", body)
        before = daemon.status()
        code2, resp2 = client._resilient_post("/check", body)
        after = daemon.status()
        assert code1 == code2 == 200
        assert resp1 == resp2
        assert after["deduped"] == before["deduped"] + 1
        assert after["requests"] == before["requests"]
        assert after["histories"] == before["histories"]
    finally:
        daemon.stop()


def test_reap_escalates_sigterm_to_sigkill_and_never_raises():
    """spawn_daemon's child-reaping satellite: SIGTERM → bounded wait
    → SIGKILL → bounded wait, and even a child stuck past SIGKILL
    must not leak TimeoutExpired into the caller's error path."""

    class _StuckProc:
        def __init__(self, dies_on_kill=True):
            self.calls = []
            self._dies_on_kill = dies_on_kill

        def terminate(self):
            self.calls.append("terminate")

        def kill(self):
            self.calls.append("kill")

        def wait(self, timeout=None):
            self.calls.append("wait")
            if "kill" in self.calls and self._dies_on_kill:
                return 0
            raise subprocess.TimeoutExpired(cmd="daemon",
                                            timeout=timeout)

    p = _StuckProc()
    serve_client._reap(p, grace_s=0.01)
    assert p.calls == ["terminate", "wait", "kill", "wait"]

    p2 = _StuckProc(dies_on_kill=False)
    serve_client._reap(p2, grace_s=0.01)
    assert p2.calls == ["terminate", "wait", "kill", "wait"]


# ---------------------------------------------------------------------------
# graceful degradation: device faults quarantine routes, reset()
# recovers the executor (windows 1 and 4)
# ---------------------------------------------------------------------------


def test_device_fault_quarantines_route_to_oracle(monkeypatch):
    """A device fault on a (kernel, E, C) route must not fail the
    batch: the route is quarantined to the CPU oracle, /status lists
    it with the error that tripped it, the quarantine metrics appear,
    and a second batch on the same routes skips the device outright."""
    from jepsen_tpu.engine import execution

    model = m.cas_register(0)
    hists = mixed_corpus(seed=7, n=3, wide=False)
    expected = wgl.check_batch(model, hists, slot_cap=32)

    def exploding_submit(self, pb):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(execution.Executor, "submit", exploding_submit)
    obs.enable(reset=True)
    daemon = CheckerDaemon(port=0)
    daemon.start(block=False)
    try:
        client = ServiceClient(port=daemon.port)
        got = client.check_batch(model, hists, slot_cap=32)
        # oracle-routed rows carry their own engine tag; the verdicts
        # themselves must be unchanged
        assert [r.get("valid?") for r in got] == [
            r.get("valid?") for r in expected]
        st = daemon.status()
        assert st["quarantine"], "route should be quarantined"
        assert all(q["route"] and q["error"] for q in st["quarantine"])
        assert st["quarantined_rows"] > 0
        assert st["errors"] == 0  # degraded, never failed
        text = client.metrics_text()
        assert "jepsen_serve_quarantine_total" in text
        assert "jepsen_serve_quarantined_routes" in text
        n_routes = len(st["quarantine"])
        got2 = client.check_batch(model, hists, slot_cap=32)
        assert [r.get("valid?") for r in got2] == [
            r.get("valid?") for r in expected]
        assert len(daemon.status()["quarantine"]) == n_routes
    finally:
        daemon.stop()
        obs.enable(reset=True)


@pytest.mark.parametrize("window", [1, 4])
def test_reset_recovers_from_mid_dispatch_device_fault(
        window, monkeypatch):
    """A device fault surfacing mid-dispatch — with earlier dispatches
    retired (window=1) or still in flight (window=4) — must leave the
    executor recoverable: reset() abandons the poisoned window entries,
    chunk map and parked escalations, and the SAME executor then
    produces clean verdicts for the next batch."""
    model = m.cas_register(0)
    hists = mixed_corpus(seed=33, n=6, wide=False)
    expected = wgl.check_batch(model, hists, slot_cap=32)

    def run_through(ex):
        ctx = RunContext(model, hists)
        planner = Planner(model, spec=ctx.spec, slot_cap=32,
                          frontier=wgl.DEFAULT_FRONTIER)
        buckets, order = planner.encode_buckets(ctx)
        for k in order:
            pb = planner.plan_rows(k, *buckets[k])
            if pb is not None:
                ex.submit(pb)
        ex.drain()
        ctx.drain_oracles()
        return ctx

    real = wgl._run_rows
    calls = {"n": 0}

    def counting(fn, mesh, arrays):
        calls["n"] += 1
        return real(fn, mesh, arrays)

    monkeypatch.setattr(wgl, "_run_rows", counting)
    ctx = run_through(Executor(window))
    assert [sig(r) for r in ctx.results] == [
        sig(r) for r in expected]
    total = calls["n"]
    assert total >= 1

    # fault the LAST dispatch of the identical (deterministic) replay:
    # everything before it is retired or in flight when it surfaces
    calls["n"] = 0

    def flaky(fn, mesh, arrays):
        calls["n"] += 1
        if calls["n"] >= total:
            raise RuntimeError("injected device fault")
        return real(fn, mesh, arrays)

    monkeypatch.setattr(wgl, "_run_rows", flaky)
    ex = Executor(window)
    with pytest.raises(RuntimeError, match="injected device fault"):
        run_through(ex)
    ex.reset()
    assert ex._win.depth == 0
    assert not ex._chunks and not ex._pending_escalations

    # the SAME executor, next batch: clean verdicts, nothing leaked
    monkeypatch.setattr(wgl, "_run_rows", real)
    ctx3 = run_through(ex)
    assert [sig(r) for r in ctx3.results] == [
        sig(r) for r in expected]
