"""The readers of the engine's inner spans, the keyed split and the
mirrored dispatch waits, on hand-built runs; and a traced run of the
keyed cell on the CPU."""

import glob
import os
from types import SimpleNamespace as NS

import _mirror
import dispatch_idle_wait_pct
import encode_ns_per_event
import engine_untraced_pct
import harness
import plan_pct
import pytest
import settle_pct
import split_pct
from _self_time import covered, self_time_ns

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def span(name, sid, t0, t1, parent=None, **attrs):
    return {"name": name, "sid": sid, "parent": parent, "t0": t0,
            "t1": t1, "pid": 1, "tid": 1,
            "attrs": {k: str(v) for k, v in attrs.items()}}


def engine_run():
    """One engine pass of 1,000 ns: two encodes (1,000 and 3,000
    events), a plan, an issue, a dispatch wait and a settle, 100 ns of
    it in no inner span."""
    return NS(spans=[
        span("engine/encode", 1, 0, 200, parent=0, events=1000),
        span("engine/encode", 2, 200, 500, parent=0, events=3000),
        span("engine/plan", 3, 500, 600, parent=0, rows=2),
        span("engine/issue", 4, 600, 650, parent=0, rows=2),
        span("engine/dispatch", 5, 650, 800, parent=0, rows=2),
        span("engine/settle", 6, 850, 950, parent=0, rows=2),
        # a grandchild inside the settle adds nothing to the cover
        span("engine/escalate", 7, 870, 900, parent=6),
        span("engine/pipeline", 0, 0, 1000),
    ], requests=[NS(t0=0.0, t1=2e-6)], profile=None)


def test_encode_ns_per_event():
    assert encode_ns_per_event.read(engine_run()) == 500 / 4000


def test_plan_and_settle_shares():
    run = engine_run()
    assert plan_pct.read(run) == pytest.approx(10.0)
    assert settle_pct.read(run) == pytest.approx(10.0)


def test_untraced_share_is_pipeline_self_time():
    run = engine_run()
    assert self_time_ns(run, "engine/pipeline") == (1000, 100)
    assert engine_untraced_pct.read(run) == pytest.approx(10.0)


def test_split_share_of_request_wall():
    run = NS(spans=[span("checker/split", 0, 0, 500, keys=3, ops=40),
                    span("engine/pipeline", 1, 600, 1500)],
             requests=[NS(t0=0.0, t1=1e-6), NS(t0=1.0, t1=1.000001)])
    assert split_pct.read(run) == pytest.approx(25.0)


def test_readers_leave_a_program_without_the_spans_unread():
    """An older program records engine/pipeline and engine/dispatch
    only: every new reader returns None, never 0 or 100."""
    run = NS(spans=[span("engine/dispatch", 1, 100, 200, parent=0),
                    span("engine/pipeline", 0, 0, 1000)],
             requests=[NS(t0=0.0, t1=1e-6)], profile=None)
    for reader in (encode_ns_per_event, plan_pct, settle_pct,
                   engine_untraced_pct, split_pct, dispatch_idle_wait_pct):
        assert reader.read(run) is None, reader.__name__


def test_covered_merges_overlaps_and_clips():
    assert covered(0, 100, [(10, 30), (20, 40), (90, 150)]) == 40
    assert covered(0, 100, []) == 0


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def test_host_events_clip_to_the_window():
    prof = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("engine/dispatch", 50, 100), ev("engine/dispatch", 300, 50),
            ev("engine/dispatch", 990, 100), ev("engine/issue", 400, 10)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            ev("engine/dispatch", 500, 10)])])])
    assert _mirror.host_events(prof, "engine/dispatch", 100, 1000) == [
        (100, 150), (300, 350), (990, 1000)]


def test_dispatch_idle_wait_reads_uncovered_wait(monkeypatch):
    """Two waits of 100 ns; chip 0 runs 120..150, chip 1 160..200 and
    300..400: 30 + 40 + 100 of the 200 ns had device work."""
    monkeypatch.setattr(dispatch_idle_wait_pct, "mirrored",
                        lambda run, name: [(100, 200), (300, 400)])
    run = NS(profile={"window": (0, 1000), "chips": [
        {"busy": [[120, 150]]}, {"busy": [[160, 200], [300, 400]]}]})
    assert dispatch_idle_wait_pct.read(run) == pytest.approx(15.0)
    # no chips in the trace (a CPU run): nothing to compare with
    run.profile["chips"] = []
    assert dispatch_idle_wait_pct.read(run) is None


def test_mirrored_without_a_trace(tmp_path):
    run = NS(profile={"window": (0, 1)})
    assert _mirror.mirrored(run, "engine/dispatch", str(tmp_path)) is None
    assert _mirror.mirrored(NS(profile=None), "engine/dispatch") is None


RECORDED = sorted(glob.glob(os.path.join(PERF, "testdata", "**",
                                         "*.xplane.pb"), recursive=True))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_trace_of_a_program_without_the_mirror(monkeypatch):
    """The chip trace in testdata predates the mirror: the reader finds
    no engine/dispatch annotation and reports nothing."""
    import xplane
    from jax.profiler import ProfileData

    red = xplane.reduce(ProfileData.from_file(RECORDED[0]))
    run = NS(profile=red)
    assert _mirror.mirrored(run, "engine/dispatch",
                            os.path.dirname(RECORDED[0])) == []
    monkeypatch.setattr(
        dispatch_idle_wait_pct, "mirrored",
        lambda r, name: _mirror.mirrored(r, name,
                                         os.path.dirname(RECORDED[0])))
    assert dispatch_idle_wait_pct.read(run) is None


def test_traced_keyed_run_reads_the_span_metrics():
    result = harness.run("keyed_test", trace=1)
    assert result["correct"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("encode_ns_per_event", "plan_pct", "settle_pct",
                 "engine_untraced_pct", "split_pct"):
        assert m.get(name) is not None and m[name] > 0, name
    assert m["engine_untraced_pct"] < 100 and m["split_pct"] < 100
    # the CPU trace has no chip to compare the waits with
    assert "dispatch_idle_wait_pct" not in m
