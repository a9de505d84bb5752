"""Observability layer (jepsen_tpu.obs): span nesting/ordering,
disabled-mode no-op cost, histogram bucketing, Chrome-trace/Prometheus
export round-trips, and an end-to-end core.run asserting phase spans +
op counters land in the store directory.  Plus regression guards for
the ADVICE r5 bench fixes that live at the obs/bench reporting seam."""

import json
import os
import sys
import threading

import pytest

from jepsen_tpu import obs
from jepsen_tpu.obs import export as export_mod
from jepsen_tpu.obs.metrics import MetricsRegistry
from jepsen_tpu.obs.tracer import NULL_SPAN, Tracer

#: the tracer module itself: the package attribute ``obs.tracer`` is
#: the accessor function, which shadows it
tracer_mod = sys.modules["jepsen_tpu.obs.tracer"]


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test starts with an empty, enabled registry/tracer and
    leaves the process-global state enabled for the next test."""
    obs.enable(reset=True)
    yield
    obs.enable(reset=True)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering():
    with obs.span("outer", cat="phase") as outer:
        with obs.span("inner", cat="op") as inner:
            assert obs.tracer().current() is inner
        with obs.span("inner2", cat="op"):
            pass
        assert obs.tracer().current() is outer

    spans = obs.tracer().finished()
    by_name = {s.name: s for s in spans}
    assert set(by_name) == {"outer", "inner", "inner2"}
    # children parent to the enclosing span; the root has no parent
    assert by_name["inner"].parent == by_name["outer"].sid
    assert by_name["inner2"].parent == by_name["outer"].sid
    assert by_name["outer"].parent is None
    # children finish before (or when) the parent does, and start after
    assert by_name["outer"].t0 <= by_name["inner"].t0
    assert by_name["inner"].t1 <= by_name["outer"].t1
    assert by_name["inner"].t1 <= by_name["inner2"].t0
    # completion order in the buffer: inner, inner2, outer
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]


def test_span_attrs_and_error_marking():
    with obs.span("a", cat="x", k="v") as sp:
        sp.set("extra", 7)
    rec = obs.tracer().finished()[0]
    assert rec.attrs == {"k": "v", "extra": "7"}

    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    rec = obs.tracer().finished()[-1]
    assert rec.name == "boom" and rec.attrs["error"] == "ValueError"


def test_spans_nest_per_thread():
    t = obs.tracer()
    seen = {}

    def worker():
        with obs.span("w", cat="op"):
            seen["parent"] = t.current().parent

    with obs.span("main-root"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    # the other thread's stack is its own: no cross-thread parenting
    assert seen["parent"] is None


def test_span_buffer_is_bounded():
    t = Tracer(enabled=True, max_spans=10)
    for i in range(25):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 10
    assert t.dropped == 15


def test_disabled_mode_allocates_nothing():
    obs.disable()
    # the disabled span is the SHARED null context — same object every
    # call, so the interpreter hot loop allocates zero records
    s1 = obs.span("x", cat="op")
    s2 = obs.span("y", cat="op")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1 as sp:
        sp.set("k", "v")  # no-op surface works
    obs.count_op("ok")
    obs.count("c_total")
    obs.observe("h_seconds", 0.1)
    obs.gauge_set("g", 1)
    assert len(obs.tracer()) == 0
    assert obs.registry().snapshot() == []
    assert obs.registry().prometheus_text() == ""


def test_disabled_engine_and_split_spans_record_nothing(monkeypatch):
    """The engine's encode/plan/issue/dispatch/settle call sites and the
    keyed checker's split, with obs off: no record, no metric and no
    profiler annotation."""
    import random

    from jepsen_tpu import independent
    from jepsen_tpu import models as m
    from jepsen_tpu.history import History, Op
    from jepsen_tpu.ops import wgl
    from jepsen_tpu.synth import generate_history as gen

    def no_annotation(name):
        raise AssertionError(f"annotation {name} opened with obs off")

    monkeypatch.setattr(tracer_mod, "_profiler_annotation", no_annotation)
    rng = random.Random(3)
    hists = [gen(rng, n_procs=3, n_ops=n, crash_p=0.0) for n in (6, 30)]
    keyed = History([
        Op(op.type, op.process, op.f, independent.kv(k, op.value))
        for k in range(3) for op in gen(rng, n_procs=3, n_ops=6)
    ]).index_ops()
    obs.disable()
    wgl.check_batch(m.cas_register(0), hists, window=2, max_dispatch=1)
    independent.batched_linearizable(m.cas_register(0)).check(
        {"store?": False}, keyed)
    assert len(obs.tracer()) == 0
    assert obs.registry().snapshot() == []


def test_spans_mirror_as_profiler_annotations(monkeypatch):
    """An enabled span opens and closes one profiler annotation of its
    own name around its interval, on its own thread."""
    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, threading.get_ident()))

        def __exit__(self, *exc):
            log.append(("exit", self.name, threading.get_ident()))

    monkeypatch.setattr(tracer_mod, "_profiler_annotation", Ann)
    with obs.span("outer", cat="engine"):
        with obs.span("inner", cat="engine"):
            pass
    me = threading.get_ident()
    assert log == [("enter", "outer", me), ("enter", "inner", me),
                   ("exit", "inner", me), ("exit", "outer", me)]


def test_spans_record_the_pid_kept_current_across_fork(monkeypatch):
    """Spans carry the process id without a system call each; the fork
    hook refreshes it in a child."""
    with obs.span("a"):
        pass
    assert obs.tracer().finished()[-1].pid == os.getpid()
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    tracer_mod._refresh_pid()  # what a forked child runs
    with obs.span("b"):
        pass
    assert obs.tracer().finished()[-1].pid == 4242
    monkeypatch.undo()
    tracer_mod._refresh_pid()
    assert tracer_mod._pid == os.getpid()


def test_obs_mirror_needs_no_jax():
    """obs imports and records spans without importing JAX; the mirror
    only starts once JAX is in the process."""
    import subprocess

    code = ("import sys\n"
            "from jepsen_tpu import obs\n"
            "tracer = sys.modules['jepsen_tpu.obs.tracer']\n"
            "obs.enable()\n"
            "with obs.span('a'): pass\n"
            "assert 'jax' not in sys.modules\n"
            "assert tracer._profiler_annotation('a') is None\n"
            "import jax\n"
            "assert tracer._profiler_annotation('a') is not None\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_histogram_bucketing():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 2.0, 3.0):
        h.observe(v)
    # le semantics: 0.01 catches 0.005 AND the exactly-0.01 sample
    assert h.cumulative() == [2, 3, 4, 6]
    assert h.count == 6
    assert h.sum == pytest.approx(5.565)
    text = reg.prometheus_text()
    assert 'lat_seconds_bucket{le="0.01"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 6' in text
    assert "lat_seconds_count 6" in text


def test_counter_gauge_labels_intern():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", type="ok")
    c2 = reg.counter("x_total", type="ok")
    assert c1 is c2  # hot paths resolve once, then reuse
    c1.inc(3)
    assert reg.value("x_total", type="ok") == 3
    g = reg.gauge("hw")
    g.set_max(5)
    g.set_max(3)
    assert reg.value("hw") == 5


# ---------------------------------------------------------------------------
# Exports
# ---------------------------------------------------------------------------


def test_chrome_trace_roundtrip(tmp_path):
    with obs.span("phase-a", cat="phase"):
        with obs.span("op-b", cat="op", f="read"):
            pass
    path = str(tmp_path / "trace.json")
    export_mod.write_chrome_trace(obs.tracer(), path)
    assert export_mod.validate_chrome_trace(path) is None
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"phase-a", "op-b"}
    for e in events:
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] >= 0
    opev = next(e for e in events if e["name"] == "op-b")
    assert opev["args"] == {"f": "read"}


def test_spans_jsonl_roundtrip(tmp_path):
    with obs.span("a", cat="c"):
        pass
    path = str(tmp_path / "spans.jsonl")
    export_mod.write_spans_jsonl(obs.tracer(), path)
    rows = [json.loads(line) for line in open(path)]
    assert rows[0]["name"] == "a" and rows[0]["t1"] >= rows[0]["t0"]


def test_prometheus_roundtrip(tmp_path):
    obs.count("jepsen_engine_rows_total", 4, engine="dense")
    obs.observe("jepsen_oracle_seconds", 0.2)
    path = str(tmp_path / "metrics.prom")
    export_mod.write_prometheus(obs.registry(), path)
    assert export_mod.validate_prometheus(path) is None
    text = open(path).read()
    assert 'jepsen_engine_rows_total{engine="dense"} 4' in text
    assert "# TYPE jepsen_oracle_seconds histogram" in text


def test_validators_reject_malformed(tmp_path):
    bad = tmp_path / "trace.json"
    bad.write_text("{}")
    assert export_mod.validate_chrome_trace(str(bad)) is not None
    bad.write_text('{"traceEvents": [{"name": "x"}]}')
    assert export_mod.validate_chrome_trace(str(bad)) is not None
    prom = tmp_path / "m.prom"
    prom.write_text("")
    assert export_mod.validate_prometheus(str(prom)) is not None
    prom.write_text("a_total{x=\"y\"} not-a-number\n")
    assert export_mod.validate_prometheus(str(prom)) is not None
    prom.write_text("a_total 3\n")
    assert export_mod.validate_prometheus(str(prom)) is None


def test_summary_folds_engines_and_ops():
    obs.count_op("ok")
    obs.count_op("ok")
    obs.count_op("fail")
    obs.count("jepsen_engine_rows_total", 7, engine="dense")
    obs.observe("jepsen_kernel_compile_seconds", 1.5, engine="dense")
    obs.observe("jepsen_kernel_execute_seconds", 0.25, engine="dense")
    obs.observe("jepsen_oracle_seconds", 0.5)
    with obs.span("generator", cat="phase"):
        pass
    s = obs.summary()
    assert s["ops"] == {"ok": 2, "fail": 1}
    assert s["engines"]["dense"]["rows"] == 7
    assert s["engines"]["dense"]["compile_s"] == pytest.approx(1.5)
    assert s["engines"]["dense"]["execute_s"] == pytest.approx(0.25)
    assert s["engines"]["oracle"]["execute_s"] == pytest.approx(0.5)
    assert [p["name"] for p in s["phases"]] == ["generator"]
    table = obs.format_summary(s)
    assert "generator" in table and "dense" in table and "2 ok" in table


# ---------------------------------------------------------------------------
# End to end: core.run on the noop workload
# ---------------------------------------------------------------------------


def _noop_run_test(tmp_path, **kw):
    from jepsen_tpu import generator as gen
    from jepsen_tpu import workloads

    t = workloads.noop_test()
    t.update(
        {
            "nodes": ["n1", "n2"],
            "concurrency": 2,
            "generator": gen.clients(
                gen.limit(12, gen.repeat({"f": "read"}))
            ),
            "store?": True,
            "store-base": str(tmp_path / "store"),
        }
    )
    t.update(kw)
    return t


def test_core_run_exports_phase_spans_and_op_counters(tmp_path):
    from jepsen_tpu import core

    result = core.run(_noop_run_test(tmp_path))
    d = os.path.join(
        str(tmp_path / "store"), "noop", result["start-time"]
    )
    # all three artifacts land beside the usual store files, valid
    trace_path = os.path.join(d, "trace.json")
    prom_path = os.path.join(d, "metrics.prom")
    assert export_mod.validate_chrome_trace(trace_path) is None
    assert export_mod.validate_prometheus(prom_path) is None
    assert os.path.exists(os.path.join(d, "trace-spans.jsonl"))

    with open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    phase_names = {e["name"] for e in events if e["cat"] == "phase"}
    assert {"setup", "generator", "teardown", "analyze"} <= phase_names
    op_events = [e for e in events if e["cat"] == "op"]
    assert len(op_events) == 12

    prom = open(prom_path).read()
    assert 'jepsen_interpreter_ops_total{type="ok"} 12' in prom

    # the summary dict is embedded in results (durable via results.json)
    with open(os.path.join(d, "results.json")) as f:
        stored = json.load(f)
    assert stored["obs"]["ops"] == {"ok": 12}
    assert any(p["name"] == "generator" for p in stored["obs"]["phases"])
    # and handed back in-memory for the CLI table
    assert result["obs-summary"]["ops"] == {"ok": 12}


def test_aborted_run_still_exports_trace(tmp_path):
    """A crash mid-run must not lose the flight recorder: the spans up
    to the abort export best-effort, like maybe_snarf_logs does for DB
    logs — that failed run is exactly what the trace is for."""
    import glob

    from jepsen_tpu import core
    from jepsen_tpu import nemesis as nemesis_mod

    class BoomNemesis(nemesis_mod.Nemesis):
        def setup(self, test):
            raise RuntimeError("boom")

    t = _noop_run_test(tmp_path)
    t["nemesis"] = BoomNemesis()
    with pytest.raises(RuntimeError, match="boom"):
        core.run(t)
    traces = glob.glob(
        str(tmp_path / "store" / "noop" / "*" / "trace.json")
    )
    assert traces, "no trace exported on the abort path"
    assert export_mod.validate_chrome_trace(traces[0]) is None


def test_core_run_obs_opt_out_records_nothing(tmp_path):
    from jepsen_tpu import core

    result = core.run(_noop_run_test(tmp_path, **{"obs?": False}))
    d = os.path.join(
        str(tmp_path / "store"), "noop", result["start-time"]
    )
    assert not os.path.exists(os.path.join(d, "trace.json"))
    assert not os.path.exists(os.path.join(d, "metrics.prom"))
    assert "obs-summary" not in result
    # the interpreter loop paid its one pre-paid branch and allocated
    # NO span records or counters
    assert len(obs.tracer()) == 0
    assert obs.registry().snapshot() == []


def test_core_run_phase_spans_align_with_history_time(tmp_path):
    """The run anchor lets exports place spans on the history time
    axis: the generator phase must bracket every op time."""
    from jepsen_tpu import core

    result = core.run(_noop_run_test(tmp_path))
    intervals = dict(
        (name, (x0, x1)) for name, x0, x1 in obs.phase_intervals()
    )
    assert "generator" in intervals
    g0, g1 = intervals["generator"]
    times = [op.time / 1e9 for op in result["history"]]
    assert times, "history empty"
    assert g0 <= min(times) + 1e-3
    assert g1 >= max(times) - 1e-3


def test_perf_graphs_carry_phase_overlay(tmp_path):
    """The perf SVGs shade completed run phases behind their series,
    aligned with history time via the run anchor."""
    from jepsen_tpu import checker as checker_mod
    from jepsen_tpu import core

    t = _noop_run_test(tmp_path)
    t["checker"] = checker_mod.compose(
        {
            "latency": checker_mod.latency_graph(),
            "rate": checker_mod.rate_graph(),
        }
    )
    result = core.run(t)
    d = os.path.join(
        str(tmp_path / "store"), "noop", result["start-time"]
    )
    svg_src = open(os.path.join(d, "latency-raw.svg")).read()
    assert "generator" in svg_src  # the phase band's label text
    rate_src = open(os.path.join(d, "rate.svg")).read()
    assert "generator" in rate_src


def test_nemesis_and_checker_spans_recorded(tmp_path):
    from jepsen_tpu import core
    from jepsen_tpu import generator as gen

    test = _noop_run_test(tmp_path)
    test["generator"] = gen.phases(
        gen.clients(gen.limit(4, gen.repeat({"f": "read"}))),
        gen.nemesis(
            gen.limit(2, gen.repeat({"f": "noop", "type": "info"}))
        ),
    )
    core.run(test)
    cats = {s.cat for s in obs.tracer().finished()}
    assert "nemesis" in cats
    assert "checker" in cats
    nem = [s for s in obs.tracer().finished(cat="nemesis")]
    assert nem and nem[0].name == "nemesis/noop"
    assert obs.registry().value(
        "jepsen_nemesis_ops_total", f="noop"
    ) == 2


def test_phase_intervals_empty_when_disabled():
    """disable() doesn't clear the buffer/anchor, so phase_intervals
    must gate on the flag — an obs-off run following an obs-on run in
    the same process must not overlay the previous run's phases."""
    obs.tracer().run_anchor_ns = obs.tracer().origin_ns
    with obs.span("generator", cat="phase"):
        pass
    assert obs.phase_intervals(), "sanity: intervals exist while enabled"
    obs.disable()
    assert obs.phase_intervals() == []


def test_chunked_first_dispatch_splits_compile_vs_execute():
    """A first check_batch larger than the dispatch cap runs many
    chunks; only the FIRST chunk traces+compiles, so the telemetry must
    record exactly one compile-phase dispatch and the rest as execute —
    not absorb the whole chunked call into compile."""
    import random

    from jepsen_tpu import models as m
    from jepsen_tpu.ops import dense, wgl
    from jepsen_tpu.synth import generate_history as gen

    # fresh fns so the first dispatch of this test really compiles
    dense._make_dense_fn_cached.cache_clear()
    wgl.make_check_fn.cache_clear()
    rng = random.Random(11)
    hists = [
        gen(rng, n_procs=3, n_ops=10, crash_p=0.0, corrupt=(i % 2 == 0))
        for i in range(6)
    ]
    wgl.check_batch(m.cas_register(0), hists, max_dispatch=2)
    reg = obs.registry()
    compiles = reg.value(
        "jepsen_kernel_dispatches_total", engine="dense", phase="compile"
    )
    executes = reg.value(
        "jepsen_kernel_dispatches_total", engine="dense", phase="execute"
    )
    assert compiles == 1, (compiles, executes)
    assert executes and executes >= 1
    # jit retraces per input shape: a NEW batch size through the same
    # cached fn is a genuine second compile, and must be labeled so
    wgl.check_batch(m.cas_register(0), hists[:3])
    assert reg.value(
        "jepsen_kernel_dispatches_total", engine="dense", phase="compile"
    ) == 2


# ---------------------------------------------------------------------------
# sliding-window metrics (fleet telemetry)
# ---------------------------------------------------------------------------


def _fake_clock(monkeypatch, start=1000.0):
    from jepsen_tpu.obs import metrics as metrics_mod

    clock = {"t": start}
    monkeypatch.setattr(metrics_mod, "_now", lambda: clock["t"])
    return clock


def test_windowed_counter_ages_out_but_cumulative_survives(monkeypatch):
    clock = _fake_clock(monkeypatch)
    reg = MetricsRegistry()
    c = reg.counter("jepsen_win_total")
    c.inc(5)
    assert c.window_sum() == 5
    clock["t"] += 30
    c.inc(2)
    assert c.window_sum() == 7  # both bursts inside the minute
    clock["t"] += 45  # the first burst is now > 60 s old
    assert c.window_sum() == 2
    clock["t"] += 600
    assert c.window_sum() == 0  # window empty...
    with c._lock:
        assert c.value == 7  # ...cumulative total untouched


def test_windowed_ring_wrap_resets_stale_slot(monkeypatch):
    from jepsen_tpu.obs.metrics import SLOT_SECONDS, WINDOW_SLOTS

    clock = _fake_clock(monkeypatch)
    reg = MetricsRegistry()
    c = reg.counter("jepsen_wrap_total")
    c.inc(9)
    # advance exactly one full ring revolution: the new slot maps to
    # the SAME ring index and must displace the stale count, not add
    clock["t"] += SLOT_SECONDS * WINDOW_SLOTS
    c.inc(3)
    assert c.window_sum() == 3


def test_windowed_histogram_totals(monkeypatch):
    clock = _fake_clock(monkeypatch)
    reg = MetricsRegistry()
    h = reg.histogram("jepsen_winlat_seconds")
    h.observe(0.5)
    h.observe(1.5)
    assert h.window_totals() == (2, 2.0)
    clock["t"] += 120
    assert h.window_totals() == (0, 0.0)
    with h._lock:
        assert h.count == 2 and h.sum == 2.0


def test_window_aggregation_helpers(monkeypatch):
    _fake_clock(monkeypatch)
    reg = MetricsRegistry()
    reg.counter("jepsen_req_total", route="a").inc(3)
    reg.counter("jepsen_req_total", route="b").inc(1)
    reg.histogram("jepsen_lat_seconds").observe(0.25)
    reg.histogram("jepsen_lat_seconds").observe(0.75)
    # rates sum across label sets, over the 60 s window
    assert reg.window_rate("jepsen_req_total") == pytest.approx(4 / 60)
    assert reg.window_rate("jepsen_lat_seconds") == pytest.approx(2 / 60)
    assert reg.window_mean("jepsen_lat_seconds") == pytest.approx(0.5)
    assert reg.window_seconds_sum("jepsen_lat_seconds") == pytest.approx(1.0)
    # never-recorded names degrade quietly
    assert reg.window_rate("jepsen_absent_total") == 0.0
    assert reg.window_mean("jepsen_absent_seconds") is None


def test_rate1m_gauges_in_exposition():
    from jepsen_tpu.obs.metrics import rate1m_name

    # the naming rule: strip the unit suffix, append _rate1m
    assert rate1m_name("jepsen_req_total") == "jepsen_req_rate1m"
    assert rate1m_name("jepsen_lat_seconds") == "jepsen_lat_rate1m"
    assert rate1m_name("jepsen_queue") == "jepsen_queue_rate1m"

    reg = MetricsRegistry()
    reg.counter("jepsen_req_total", route="a").inc(6)
    reg.histogram("jepsen_lat_seconds").observe(0.1)
    reg.gauge("jepsen_depth").set(3)
    text = reg.prometheus_text()
    assert "# TYPE jepsen_req_rate1m gauge" in text
    assert 'jepsen_req_rate1m{route="a"} 0.1' in text  # 6/60 s
    assert "# TYPE jepsen_lat_rate1m gauge" in text
    # gauges are instantaneous already: no synthesized rate family
    assert "jepsen_depth_rate1m" not in text
    assert export_mod.validate_prometheus_text(text) is None


def test_series_cardinality_cap_folds_overflow():
    from jepsen_tpu.obs.metrics import SERIES_DROPPED

    reg = MetricsRegistry(max_series=3)
    for i in range(5):
        reg.counter("jepsen_cap_total", k=str(i)).inc()
    fam = [d for d in reg.snapshot() if d["name"] == "jepsen_cap_total"]
    # 3 real series + ONE overflow series holding the folded tail
    assert len(fam) == 4
    by_labels = {tuple(sorted(d["labels"].items())): d for d in fam}
    assert by_labels[(("overflow", "1"),)]["value"] == 2
    assert reg.value(SERIES_DROPPED) == 2
    # the fold is sticky: later novel label sets keep landing there
    reg.counter("jepsen_cap_total", k="99").inc()
    assert by_labels != {}  # unchanged real series
    assert reg.value(SERIES_DROPPED) == 3
    # the drop counter itself and the overflow series are exempt from
    # the cap (no recursion, the evidence can always be recorded)
    assert export_mod.validate_prometheus_text(
        reg.prometheus_text()) is None


def test_max_series_env_override(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_OBS_MAX_SERIES", "7")
    reg = MetricsRegistry()
    assert reg.max_series == 7
    monkeypatch.setenv("JEPSEN_TPU_OBS_MAX_SERIES", "not-a-number")
    from jepsen_tpu.obs.metrics import DEFAULT_MAX_SERIES

    assert MetricsRegistry().max_series == DEFAULT_MAX_SERIES
