"""The trace reduction: interval arithmetic on a made-up profile, and
the whole reduction on a small trace recorded on one v5e chip."""

import glob
import os
from types import SimpleNamespace as NS

import pytest
import xplane as perf_trace
from _kernels import dense_seconds

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


def made_up():
    host = plane("/host:CPU", {"python": [
        ev("perf/request", 100, 400, idx=0),
        ev("perf/request", 600, 300, idx=1)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion", 50, 100),      # clipped to 100..150
                    ev("while", 200, 100), ev("add", 250, 100),
                    ev("fusion", 700, 50)],
        "XLA Modules": [ev("jit_check_one(1)", 200, 150),
                        ev("jit_other(2)", 700, 50)]})
    return NS(planes=[host, dev, plane("/device:TPU:0 SparseCore", {})])


def test_union_and_window():
    red = perf_trace.reduce(made_up())
    assert red["window"] == (100, 900)
    (chip,) = red["chips"]
    # 100..150, 200..350, 700..750
    assert chip["busy_ns"] == 50 + 150 + 50
    busy, window = perf_trace.busy_and_window_s(red)
    assert busy == 250e-9 and window == 800e-9
    assert dense_seconds(NS(profile=red)) == 150e-9


def test_gaps_named_by_host():
    red = perf_trace.reduce(made_up())
    spans = [(100, 500, "request"), (380, 460, "engine host")]
    gaps = perf_trace.idle_gaps(red, spans)
    # gaps: 150..200 (50), 350..700 (350), 750..900 (150)
    assert [round(g * 1e9) for _n, g in gaps] == [350, 150, 50]
    assert gaps[0][0] == "outside requests"   # midpoint 525
    assert gaps[2][0] == "request"
    assert perf_trace.top_ops(red)[0][0] == "fusion"


RECORDED = sorted(glob.glob(os.path.join(PERF, "testdata", "**",
                                         "*.xplane.pb"), recursive=True))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    red = perf_trace.reduce(ProfileData.from_file(RECORDED[0]))
    assert red is not None and len(red["requests"]) >= 1
    assert len(red["chips"]) == 1
    busy, window = perf_trace.busy_and_window_s(red)
    assert 0 < busy < window
    # an executable's run spans its ops and the short gaps between them
    assert busy <= dense_seconds(NS(profile=red)) <= window
    gaps = perf_trace.idle_gaps(red, [])
    assert sum(g for _n, g in gaps) <= window - busy + 1e-9


def test_host_spans_name_every_program_span():
    """Every finished span of the program names the gaps it covers, by
    its own name but for the engine's three; the requests go first."""
    import run as perf_run

    run = NS(requests=[NS(mono0=0)], spans=[
        {"name": "checker/split", "t0": 10, "t1": 50},
        {"name": "engine/pipeline", "t0": 50, "t1": 90},
        {"name": "engine/encode", "t0": 60, "t1": 70},
        {"name": "engine/dispatch", "t0": 80, "t1": None}])
    spans = perf_run.host_spans(run, {"requests": [(100, 200, 0)]})
    assert spans == [(100, 200, "request: harness and checker seam"),
                     (110, 150, "checker/split"),
                     (150, 190, "engine host: encode, plan, settle"),
                     (160, 170, "engine/encode")]
