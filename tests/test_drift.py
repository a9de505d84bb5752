"""Drift-sentinel and profiling tests.

The sentinel (jepsen_tpu/obs/drift.py) scores dispatch-journal rows
against the cost model's prediction — these tests pin the residual
math (deterministic EWMA, median normalization), the
tolerate-anything row handling (old schemas and unpriceable shapes
must become skip counters, never NaN ratios), the exactly-once
threshold-crossing latch with its durable journal marker, and the
profiling capture round-trip.
"""

import math
import os

import pytest

from jepsen_tpu.obs import drift
from jepsen_tpu.obs import journal
from jepsen_tpu.obs import profiling


def _row(**over):
    """A schema-shaped journal row whose execute_s defaults to exactly
    the analytic proxy × 1e-6 — ratio 1.0 by construction."""
    base = dict(
        kernel="dense", E=8, C=2, F=0, rows=256, n_devices=1,
        mesh_shape=[1], window=4, compile_s=0.0,
        coalesced=1, cache="hit", closure_mode="", union="",
        calibration="", trace_id="",
    )
    base.update(over)
    if "execute_s" not in base:
        try:
            base["execute_s"] = drift.analytic_proxy(
                base["kernel"], base["E"], base["C"], base["F"],
                base["rows"]) * 1e-6
        except TypeError:  # deliberately malformed shape fields
            base["execute_s"] = 0.002
    return base


def _feed(sentinel, E, scale, n=1):
    for _ in range(n):
        proxy = drift.analytic_proxy("dense", E, 2, 0, 256)
        reason = sentinel.observe_row(
            _row(E=E, execute_s=proxy * scale * 1e-6))
        assert reason is None
# ---------------------------------------------------------------------------
# residual math
# ---------------------------------------------------------------------------


def test_analytic_proxy_mirrors_planning_fallback():
    assert drift.analytic_proxy("dense", 8, 2, 0, 256) == 256 * 8
    assert drift.analytic_proxy("cycles", 4, 0, 2, 3) == 3 * 4 * 4 * 2
    # frontier: words = ceil(E/32)
    assert drift.analytic_proxy("frontier", 33, 2, 4, 5) == 5 * 4 * 3 * 2
    assert drift.analytic_proxy("unknown", 0, 0, 0, 7) == 7.0


def test_ewma_is_deterministic():
    s = drift.DriftSentinel(threshold=100.0)
    proxy = drift.analytic_proxy("dense", 8, 2, 0, 256)
    for scale in (1.0, 2.0, 1.0):
        assert s.observe_row(_row(execute_s=proxy * scale * 1e-6)) is None
    st = s._shapes[("dense", 8, 2, 0)]
    # seeded with the first ratio, then alpha=0.3 smoothing
    r1 = 1e-6
    r2 = 0.3 * 2e-6 + 0.7 * r1
    r3 = 0.3 * 1e-6 + 0.7 * r2
    assert st.ewma == pytest.approx(r3)
    assert st.n == 3
    # snapshots are pure reads: repeated calls agree exactly
    assert s.snapshot() == s.snapshot()


def test_median_normalization_flags_only_the_inflated_shape():
    s = drift.DriftSentinel(threshold=2.0, min_samples=3)
    for E in (8, 16, 32):
        _feed(s, E, 1.0, n=3)
    _feed(s, 64, 3.0, n=3)
    snap = s.snapshot()
    assert snap["score"] == pytest.approx(3.0, rel=0.01)
    assert [sh["E"] for sh in snap["stale"]] == [64]
    assert snap["retune_recommended"] is True
    assert snap["rows_scored"] == 12


def test_min_samples_gates_the_score():
    s = drift.DriftSentinel(threshold=2.0, min_samples=3)
    for E in (8, 16, 32):
        _feed(s, E, 1.0, n=3)
    _feed(s, 64, 3.0, n=2)  # one short of min_samples
    snap = s.snapshot()
    assert snap["stale"] == []
    assert snap["retune_recommended"] is False


# ---------------------------------------------------------------------------
# hardening: old schemas + unpriceable shapes → skip counters, never NaN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("row,reason", [
    ("not a dict", "not-dict"),
    (["also", "not"], "not-dict"),
    ({"kernel": drift.MARKER_KERNEL, "rows": 0}, "marker"),
    ({}, "no-shape"),
    ({"kernel": "dense"}, "no-shape"),                 # pre-v1 partial row
    (_row(E="eight"), "no-shape"),
    (_row(E=None), "no-shape"),
    (_row(rows=0), "no-shape"),
    (_row(C=-1), "no-shape"),
    (_row(cache="miss", compile_s=0.01, execute_s=0.0), "not-hit"),
    ({k: v for k, v in _row().items() if k != "cache"}, "not-hit"),
    (_row(execute_s=0.0), "not-timed"),
    (_row(execute_s=-1.0), "not-timed"),
    (_row(execute_s=float("nan")), "not-timed"),
    (_row(execute_s=float("inf")), "not-timed"),
    (_row(execute_s="fast"), "not-timed"),
    ({k: v for k, v in _row().items() if k != "execute_s"}, "not-timed"),
])
def test_malformed_row_table(row, reason):
    s = drift.DriftSentinel(threshold=2.0)
    assert s.observe_row(row) == reason
    snap = s.snapshot()
    assert snap["rows_skipped"] == {reason: 1}
    assert snap["rows_scored"] == 0
    assert math.isfinite(snap["score"]) and snap["score"] == 1.0
    assert reason in drift.SKIP_REASONS


def test_unpriceable_shape_skips_as_no_estimate(monkeypatch):
    s = drift.DriftSentinel(threshold=2.0)
    monkeypatch.setattr(drift, "predicted_seconds",
                        lambda *a: (None, "proxy"))
    assert s.observe_row(_row()) == "no-estimate"
    monkeypatch.setattr(drift, "predicted_seconds",
                        lambda *a: (float("inf"), "proxy"))
    assert s.observe_row(_row()) == "bad-ratio"
    snap = s.snapshot()
    assert snap["rows_scored"] == 0
    assert math.isfinite(snap["score"])


def test_old_schema_row_with_shape_still_scores():
    # a hypothetical older row missing trace_id/union/etc: the sentinel
    # only needs the shape, the cache phase, and the measured seconds
    s = drift.DriftSentinel(threshold=2.0)
    old = {"kernel": "dense", "E": 8, "C": 2, "F": 0, "rows": 256,
           "cache": "hit", "execute_s": 0.002048}
    assert s.observe_row(old) is None
    assert s.snapshot()["rows_scored"] == 1


# ---------------------------------------------------------------------------
# threshold crossing: exactly once per episode, durable journal marker
# ---------------------------------------------------------------------------


def test_crossing_latches_once_per_episode(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    journal.configure(jpath)
    try:
        s = drift.DriftSentinel(threshold=2.0, min_samples=3)
        for E in (8, 16, 32):
            _feed(s, E, 1.0, n=3)
        _feed(s, 64, 3.0, n=3)          # first crossing
        assert s.snapshot()["crossings"] == 1
        _feed(s, 64, 3.0, n=4)          # sustained: still one episode
        assert s.snapshot()["crossings"] == 1
        _feed(s, 64, 1.0, n=4)          # EWMA decays below threshold
        snap = s.snapshot()
        assert snap["retune_recommended"] is False
        assert snap["crossings"] == 1
        _feed(s, 64, 4.0, n=3)          # second episode
        snap = s.snapshot()
        assert snap["retune_recommended"] is True
        assert snap["crossings"] == 2

        rows = list(journal.read_rows(jpath))
        markers = [r for r in rows if r["kernel"] == drift.MARKER_KERNEL]
        assert len(markers) == 2        # one durable marker per episode
        assert all(m["rows"] == 0 for m in markers)
        assert all("drift-score=" in m["trace_id"] for m in markers)
        # the marker is schema-valid AND self-skipping on rescan
        assert all(journal.validate_row(m) for m in markers)
        s2 = drift.DriftSentinel(threshold=2.0, min_samples=3)
        assert s2.observe_row(markers[0]) == "marker"
    finally:
        journal.configure(None)


def test_marker_not_emitted_without_a_journal():
    s = drift.DriftSentinel(threshold=2.0, min_samples=3)
    for E in (8, 16, 32):
        _feed(s, E, 1.0, n=3)
    _feed(s, 64, 3.0, n=3)  # crossing with journal off: no crash
    assert s.snapshot()["crossings"] == 1


def test_scan_warm_starts_from_a_journal_file(tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    journal.configure(jpath)
    try:
        for E in (8, 16, 32):
            for _ in range(3):
                proxy = drift.analytic_proxy("dense", E, 2, 0, 256)
                assert journal.emit(**_row(
                    E=E, execute_s=proxy * 1e-6)) is not None
        for _ in range(3):
            proxy = drift.analytic_proxy("dense", 64, 2, 0, 256)
            assert journal.emit(**_row(
                E=64, execute_s=proxy * 3e-6)) is not None
    finally:
        journal.configure(None)
    s = drift.DriftSentinel(threshold=2.0, min_samples=3)
    assert s.scan(jpath) == 12
    snap = s.snapshot()
    assert [sh["E"] for sh in snap["stale"]] == [64]
    assert snap["retune_recommended"] is True


def test_module_singleton_configure_and_disable():
    try:
        s = drift.configure(threshold=5.0)
        assert drift.active() is s
        assert s.threshold == 5.0
    finally:
        drift.disable()
    assert drift.active() is None


def test_env_threshold(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_DRIFT_THRESHOLD", "3.5")
    assert drift.DriftSentinel().threshold == 3.5
    monkeypatch.setenv("JEPSEN_TPU_DRIFT_THRESHOLD", "0.5")  # must exceed 1
    assert drift.DriftSentinel().threshold == drift.DEFAULT_THRESHOLD
    monkeypatch.setenv("JEPSEN_TPU_DRIFT_THRESHOLD", "junk")
    assert drift.DriftSentinel().threshold == drift.DEFAULT_THRESHOLD


# ---------------------------------------------------------------------------
# profiling capture round-trip
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not profiling.capture_available(),
                    reason="jax.profiler capture unavailable")
def test_profile_capture_round_trip(tmp_path):
    out = str(tmp_path / "cap")
    manifest = profiling.capture(out, seconds=0.01, label="t")
    loaded = profiling.load_manifest(out)
    assert loaded == manifest
    assert loaded["label"] == "t"
    assert loaded["idle"] is True
    assert isinstance(loaded["memory"], list)
    assert os.path.exists(os.path.join(out, profiling.MANIFEST))


def test_profile_capture_propagates_work_errors(tmp_path):
    out = str(tmp_path / "cap")
    with pytest.raises(ValueError):
        profiling.capture(out, work=lambda: (_ for _ in ()).throw(
            ValueError("boom")))
    # the manifest still landed (trace stopped, inventory sampled)
    loaded = profiling.load_manifest(out)
    assert loaded is not None and loaded["idle"] is False
