"""The device-owning **execution** half of the checker engine.

Everything here touches shared, stateful resources: the bounded
:class:`DispatchWindow` of in-flight device dispatches, the compiled-
kernel cache (via ``wgl``'s claim helpers), the escalation ladder, and
the hand-off to the CPU-oracle worker pool.  The pure per-run half —
encode, bucketing, kernel planning — lives in
:mod:`jepsen_tpu.engine.planning`.

An :class:`Executor` is the unit of device ownership.  The in-process
pipeline (:func:`jepsen_tpu.engine.pipeline.run`) creates a private
one per run; the checker service daemon (:mod:`jepsen_tpu.serve`)
keeps ONE resident executor alive across runs, feeding it planned
buckets whose rows come from many concurrent client contexts — the
jit cache and the window stay warm, and same-shape rows from
different runs ride the same dispatch.

Both the window and the executor are **owner-thread confined**: all
``submit``/``drain`` calls must come from the thread that created
them (runtime-enforced by :meth:`DispatchWindow._check_owner`).  The
oracle worker pool interacts with execution only through Futures held
by each run's :class:`~jepsen_tpu.engine.planning.RunContext`, never
by driving the window.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import obs
from ..obs import drift as obs_drift
from ..obs import journal as obs_journal


def row_bucket_target(n: int) -> int:
    """Row count → its stable dispatch shape: the next power of two,
    floored at :func:`row_bucket_floor` (the calibration-aware floor;
    :data:`ROW_BUCKET` untuned)."""
    target = row_bucket_floor()
    while target < n:
        target *= 2
    return target


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def shard_row_target(n: int, n_shards: int) -> int:
    """Row count → its stable dispatch shape on an ``n_shards``-device
    mesh: the PER-SHARD row count rounds to its power-of-two bucket,
    floored so the GLOBAL shape never drops below
    :func:`row_bucket_floor` (the same floor the single-device path
    uses — a tiny batch pays
    the same ~64 neutral rows it always did, spread across the slice,
    not 64 per chip).  Keying the bucket on per-shard rows is what
    keeps jit executables stable as traffic varies: under
    ``shard_map`` the traced shape is the per-device shard, so 500
    rows and 400 rows on 8 devices both trace the 64-row-per-chip
    kernel.  ``n_shards=1`` degenerates to :func:`row_bucket_target`
    exactly, and the result is always divisible by ``n_shards``."""
    if n_shards <= 1:
        return row_bucket_target(n)
    per_floor = _pow2_at_least(max(1, -(-row_bucket_floor() // n_shards)))
    per = max(per_floor, _pow2_at_least(max(1, -(-n // n_shards))))
    return n_shards * per

#: default bound on concurrently in-flight device dispatches; 1 = the
#: strictly serial dispatch-sync-dispatch path
DEFAULT_WINDOW = 4

#: minimum dispatch row bucket: row counts round up to the next power
#: of two ≥ this (never past the chunk cap) with neutral all-padding
#: rows, so jit executables are keyed by STABLE shapes — two runs of
#: ~500 subhistories both dispatch at 512 rows and hit one compiled fn
#: instead of retracing at 506 vs 493.  Geometric buckets bound the
#: executable count at O(log max_dispatch) per (E, C) shape while
#: wasting < 2× rows of (cheap, neutral) padding — the trade every
#: serving stack makes, and what keeps the resident checker service's
#: warm path warm across real varying-size traffic.  In-process
#: one-shot runs pay at most one compile either way.
ROW_BUCKET = 64


def row_bucket_floor() -> int:
    """The resolved minimum dispatch row bucket:
    ``JEPSEN_TPU_ENGINE_ROW_BUCKET`` > active calibration
    (doc/tuning.md) > :data:`ROW_BUCKET`.  Always a power of two — a
    non-pow2 override rounds up so the geometric bucket ladder stays
    intact."""
    from ..tune import artifact as _cal

    return _cal.resolve_knob(
        "JEPSEN_TPU_ENGINE_ROW_BUCKET",
        lambda v: _pow2_at_least(max(1, int(v))),
        lambda cal: cal.row_bucket(),
        ROW_BUCKET,
    )


def default_window() -> int:
    """Resolved in-flight window: ``JEPSEN_TPU_ENGINE_WINDOW`` >
    active calibration (doc/tuning.md) > :data:`DEFAULT_WINDOW`."""
    from ..tune import artifact as _cal

    return _cal.resolve_knob(
        "JEPSEN_TPU_ENGINE_WINDOW",
        lambda v: max(1, int(v)),
        lambda cal: max(1, cal.window()),
        DEFAULT_WINDOW,
    )


def _materialize(out):
    """Force device work to the host (the sync point)."""
    if isinstance(out, (tuple, list)):
        return tuple(np.asarray(x) for x in out)
    return np.asarray(out)


class DispatchWindow:
    """A bounded window of in-flight device dispatches.

    ``submit(key, thunk)`` first retires (syncs) the oldest entries
    until fewer than ``window`` are in flight, then calls ``thunk`` —
    which must *dispatch* device work and return the lazy device
    arrays — and enqueues its result.  ``drain()`` retires everything
    left.  Retirement materializes the arrays via ``np.asarray`` and
    hands ``(key, materialized, t_dispatch)`` to ``on_retire`` (also
    returned from ``submit``/``drain`` for callers that prefer pull).

    window=1 is the serial contract: every dispatch fully settles
    before the next one is issued, reproducing the historical
    dispatch-sync-dispatch path exactly.  The window is shared
    machinery — ``check_batch`` dispatches bucket chunks through it,
    and ``ops.cycles`` its Elle screen buckets.

    A window is **owner-thread confined** (``# jt: guarded-by
    (owner-thread)`` on its state, checked by the lock-discipline lint
    pass): the in-flight deque and depth/count bookkeeping are
    deliberately lock-free, so ``submit``/``drain`` refuse calls from
    any thread but the creating one rather than corrupt them silently
    — the oracle worker pool must interact with the engine only
    through Futures (see the pipeline's stage-3 drain), never by
    driving the window.

    Time spent blocked in retirement is recorded as
    ``jepsen_engine_bubble_seconds`` and an ``engine/dispatch`` span;
    the post-submit depth feeds the ``jepsen_engine_inflight_depth``
    high-water gauge.  The dispatch call is an ``engine/issue`` span
    and ``on_retire`` an ``engine/settle`` span.
    """

    def __init__(
        self,
        window: Optional[int] = None,
        on_retire: Optional[Callable[[Any, Any, float], None]] = None,
    ):
        self.window = max(
            1, int(window) if window is not None else default_window()
        )
        self.on_retire = on_retire
        #: (key, lazy-out, t_dispatch, attrs)
        self._inflight: deque = deque()  # jt: guarded-by(owner-thread)
        self.peak_depth = 0  # jt: guarded-by(owner-thread)
        self.submitted = 0  # jt: guarded-by(owner-thread)
        self._owner = threading.get_ident()

    def _check_owner(self) -> None:
        if threading.get_ident() != self._owner:
            raise RuntimeError(
                "DispatchWindow is owner-thread confined: submit/drain "
                "must run on the creating thread (oracle workers hand "
                "results back through Futures, never drive the window)"
            )

    @property
    def depth(self) -> int:
        return len(self._inflight)

    def submit(self, key, thunk, attrs: Optional[dict] = None) -> list:
        """Dispatch one unit of device work; returns entries retired to
        make room (empty until the window fills)."""
        self._check_owner()
        retired = []
        while len(self._inflight) >= self.window:
            retired.append(self._retire())
        # stamp BEFORE the thunk: jit trace + XLA compile run
        # synchronously inside the first dispatch call, and the
        # compile-vs-execute histograms must keep containing them
        t_dispatch = time.perf_counter()
        # enqueue + host-to-device copy, and the compile on a miss
        with obs.span("engine/issue", cat="engine") as sp:
            out = thunk()
            if sp and attrs:
                sp.set("rows", attrs.get("rows"))
        self._inflight.append((key, out, t_dispatch, attrs))
        self.submitted += 1
        depth = len(self._inflight)
        if depth > self.peak_depth:
            self.peak_depth = depth
        obs.gauge_max("jepsen_engine_inflight_depth", depth)
        return retired

    def _retire(self):
        key, out, t_dispatch, attrs = self._inflight.popleft()
        t0 = time.perf_counter()
        if obs.enabled():
            with obs.span(
                "engine/dispatch", cat="engine", **(attrs or {})
            ):
                mat = _materialize(out)
        else:
            mat = _materialize(out)
        wait = time.perf_counter() - t0
        obs.observe("jepsen_engine_bubble_seconds", wait)
        if self.on_retire is not None:
            with obs.span("engine/settle", cat="engine") as sp:
                self.on_retire(key, mat, t_dispatch)
                if sp and attrs:
                    sp.set("rows", attrs.get("rows"))
        return key, mat, t_dispatch

    def drain(self) -> list:
        """Retire every in-flight dispatch, oldest first."""
        self._check_owner()
        out = []
        while self._inflight:
            out.append(self._retire())
        return out

    def abandon(self) -> int:
        """Drop every in-flight entry WITHOUT retiring (no host sync,
        no ``on_retire``): the recovery path after a dispatch raised —
        syncing the survivors could re-raise the same device failure.
        The dropped device computations finish (or die) on their own
        and get collected.  Returns the number dropped."""
        self._check_owner()
        n = len(self._inflight)
        self._inflight.clear()
        return n


class Executor:
    """Device-owning execution of planned buckets.

    ``submit(planned_bucket)`` splits the bucket into footprint-safe
    chunks and dispatches them through the executor's bounded
    :class:`DispatchWindow`; ``drain()`` retires everything in flight
    and runs the deferred escalation ladder.  Row verdicts route
    through each row's ``(ctx, idx)`` token back to its
    :class:`~jepsen_tpu.engine.planning.RunContext` — rows from many
    concurrent runs can share one dispatch (the service's cross-run
    coalescing) without any result cross-talk.

    Safety under pipelining (inherited verbatim from the pipeline it
    was factored out of): the frontier footprint budget
    (``fn.safe_dispatch`` ← ``FRONTIER_DISPATCH_BUDGET``) is
    crash-calibrated for ONE in-flight dispatch **on one chip**, so
    with a window of W each frontier chunk takes 1/W of the safe rows
    — total in-flight HBM stays at the calibrated bound no matter how
    many client runs coalesce.  Shapes whose cap floors out below W
    dispatch strictly serially at the full single-dispatch cap.  Dense
    chunks keep the full cap: the kernel is overflow-free with a small
    per-row footprint, and multi-in-flight dense dispatch IS the
    measured flagship bench pattern.  Escalation reruns dispatch only
    while the window is empty (see :meth:`drain`).

    **Slice-native dispatch** (doc/checker-engines.md): with a mesh of
    n devices (an explicit ``mesh=`` or, when none is passed, the
    auto-resolved :func:`~jepsen_tpu.parallel.mesh.engine_default_mesh`
    — every attached device whenever more than one is present), every
    budget above is PER CHIP: chunk caps scale to ``n × per-chip
    safe_dispatch`` because ``shard_map`` splits each dispatch's rows
    evenly across the mesh, so no single chip ever holds more
    concurrent rows than the crash-calibrated single-chip cap — never
    a shared global pool that one chip could drain.  Chunk row counts
    pad to a device multiple (via the per-shard power-of-two bucket,
    :func:`shard_row_target`) with neutral all-padding rows sliced
    back at settle, so verdicts are untouched and non-divisible
    batches never retrace.  Per-chip in-flight rows are tracked in
    :attr:`chip_row_accounting` — the hook the budget tests assert on.

    Owner-thread confined like its window: create it on the thread
    that will drive it (the service daemon builds its resident
    executor ON the device thread, never on a request handler).
    """

    def __init__(
        self,
        window: Optional[int] = None,
        *,
        mesh=None,
        escalation=None,
        sufficient_rung: bool = True,
        max_dispatch: Optional[int] = None,
    ):
        from ..ops import wgl

        if mesh is None:
            from ..parallel import mesh as mesh_mod

            mesh = mesh_mod.engine_default_mesh()
        self.mesh = mesh
        self.escalation = (
            wgl.ESCALATION_FACTORS if escalation is None else escalation
        )
        self.sufficient_rung = sufficient_rung
        self.max_dispatch = (
            wgl.DEFAULT_MAX_DISPATCH if max_dispatch is None else max_dispatch
        )
        self._win = DispatchWindow(window, on_retire=self._settle_chunk)
        #: chunk_id -> {plan, arrays, rows, n, phase}
        self._chunks: Dict[int, dict] = {}  # jt: guarded-by(owner-thread)
        self._next_chunk = 0  # jt: guarded-by(owner-thread)
        #: chunks whose base pass overflowed, parked until the window
        #: drains: escalation reruns dispatch at LARGER capacities, and
        #: stacking one on top of `window` in-flight base dispatches
        #: would hold more concurrent footprint than the
        #: crash-calibrated per-dispatch budget was measured for.
        #: Deferring also matches the serial path's order (escalate
        #: after the base pass).  Overflow is the rare path; the
        #: common all-resolved chunk settles immediately.
        self._pending_escalations: List[tuple] = []  # jt: guarded-by(owner-thread)
        #: cumulative dispatch phases — the service's warm-hit
        #: accounting reads (and diffs) these across request batches
        self.phase_counts = {"compile": 0, "execute": 0}
        #: in-flight PER-CHIP rows and their peaks, keyed by
        #: (kernel, E, C, frontier, per-chip cap) — the accounting
        #: hook the per-chip budget acceptance tests assert on: for
        #: every frontier shape, peak ≤ its single-chip cap at any
        #: window depth (dense is allowed cap × window by design)
        self._chip_rows_inflight: Dict[int, int] = {}  # jt: guarded-by(owner-thread)
        self.chip_row_accounting: Dict[int, dict] = {}  # jt: guarded-by(owner-thread)
        #: per-device live/dispatched row totals (device occupancy)
        self._dev_rows_live: List[int] = [0] * self.n_devices
        self._dev_rows_total: List[int] = [0] * self.n_devices
        #: extra per-dispatch journal fields the caller owns (the serve
        #: daemon sets coalesced-run count + trace ids per group; the
        #: device thread is the only mutator, so no guard)
        self.journal_context: Dict[str, Any] = {}
        #: optional ``(plan, arrays, disp_shape)`` callback fired on
        #: every COLD dispatch (first claim of ``(fn, shape)``) — the
        #: serve daemon's AOT executable cache records the compile here
        #: so a restarted daemon can pre-warm the shape.  Runs on the
        #: owner thread inside the dispatch path; exceptions are
        #: swallowed (cache bookkeeping must never fail a dispatch).
        self.on_cold_compile: Optional[Callable[[Any, Any, Any], None]] = None

    # -- stats the pipeline's telemetry reads -----------------------------

    @property
    def n_devices(self) -> int:
        """Devices the engine shards each dispatch across (1 = no mesh)."""
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    @property
    def window_size(self) -> int:
        return self._win.window

    @property
    def submitted(self) -> int:
        return self._win.submitted

    @property
    def peak_depth(self) -> int:
        return self._win.peak_depth

    # -- settle path (runs inside window retirement, owner thread) -------

    def _settle_chunk(self, chunk_id, mat, t_dispatch):
        # on_retire runs synchronously inside the owner-checked
        # submit/drain (DispatchWindow._retire), never on a foreign
        # thread, so owner-thread state stays confined
        ch = self._chunks.pop(chunk_id)  # jt: allow[lock-thread-confined] — synchronous on_retire, owner thread
        plan = ch["plan"]
        n_live = ch["n"]
        fnk = ch["acct_key"]
        left = self._chip_rows_inflight.get(fnk, 0) - ch["chip_rows"]  # jt: allow[lock-thread-confined] — synchronous on_retire, owner thread
        self._chip_rows_inflight[fnk] = max(0, left)  # jt: allow[lock-thread-confined] — synchronous on_retire, owner thread
        elapsed = time.perf_counter() - t_dispatch
        if obs.enabled():
            # dispatch-to-materialized latency, split compile (first
            # dispatch of this fn at this shape: trace + XLA compile +
            # execute) vs execute (cache-hit) exactly as the serial
            # path recorded it — under pipelining these overlap, so
            # their sum can exceed wall clock by design
            obs.observe(
                f"jepsen_kernel_{ch['phase']}_seconds",
                elapsed,
                engine=plan.kernel,
            )
        if obs_journal.active() is not None:
            self._journal_dispatch(plan, ch, elapsed)
        settle = getattr(plan, "settle_rows", None)
        if settle is not None:
            # self-settling plan (the Elle cycle screens): the plan
            # owns its output contract — no escalation ladder, no
            # ok/failed_at/overflow unpack; it slices live rows itself
            settle(ch["rows"], mat, n_live)
            return
        # np.array (not asarray): jax outputs are read-only views and
        # the escalation pass writes back into these
        ok, failed_at, overflow = (np.array(x)[:n_live] for x in mat)
        if overflow.any():
            self._pending_escalations.append(  # jt: allow[lock-thread-confined] — synchronous on_retire, owner thread
                (plan, ch["arrays"], ch["rows"], ok, failed_at, overflow)
            )
        else:
            self._assign_rows(plan, ch["rows"], ok, failed_at, overflow)

    def _journal_dispatch(self, plan, ch: dict, elapsed: float) -> None:
        """One pinned-schema journal row per settled dispatch
        (obs.journal): the durable per-dispatch telemetry stream behind
        the learned cost model and on-TPU bench windows.  Best-effort —
        journal failures never fail a dispatch (emit() swallows them)."""
        from ..ops import dense
        from ..tune import artifact as _cal

        cal = _cal.active()
        compile_hit = ch["phase"] == "compile"
        ctx = self.journal_context
        row = obs_journal.emit(
            kernel=str(plan.kernel),
            E=int(getattr(plan, "E", 0) or 0),
            C=int(getattr(plan, "C", 0) or 0),
            F=int(getattr(plan, "frontier", 0) or 0),
            rows=int(ch["n"]),
            n_devices=int(self.n_devices),
            mesh_shape=(list(self.mesh.devices.shape)
                        if self.mesh is not None else [1]),
            window=int(self.window_size),
            compile_s=round(elapsed, 6) if compile_hit else 0.0,
            execute_s=0.0 if compile_hit else round(elapsed, 6),
            coalesced=int(ctx.get("coalesced", 1)),
            cache="miss" if compile_hit else "hit",
            closure_mode=str(getattr(plan, "closure_mode", "") or ""),
            union=(dense._union_mode() if plan.kernel == "dense" else ""),
            calibration=(cal.calibration_id if cal is not None else ""),
            trace_id=str(ctx.get("trace_id", "") or ""),
        )
        if row is not None:
            # drift sentinel rides the journal stream: score the
            # settled row's measured cost against the model's estimate
            # (obs.drift — observation only, never a dispatch decision)
            sentinel = obs_drift.active()
            if sentinel is not None:
                sentinel.observe_row(row)

    def _settle_rows(self, plan, arrays, rows, ok, failed_at, overflow):
        """Escalate a chunk's overflows on-device, then assign verdicts
        (still-overflowed rows join each row's oracle pool): the
        deferred escalation pass of :meth:`drain`, and the inline
        settle of a bucket no kernel can run."""
        from ..ops import wgl

        with obs.span("engine/settle", cat="engine") as sp:
            wgl.escalate_overflows(
                plan, arrays, ok, failed_at, overflow,
                mesh=self.mesh, escalation=self.escalation,
                sufficient_rung=self.sufficient_rung,
                max_dispatch=self.max_dispatch,
            )
            self._assign_rows(plan, rows, ok, failed_at, overflow)
            if sp:
                sp.set("rows", len(rows))

    def _assign_rows(self, plan, rows, ok, failed_at, overflow):
        unresolved = "routed" if plan.kernel == "oracle" else "overflow"
        for row, (ctx, hist_idx) in enumerate(rows):
            if overflow[row]:
                # still overflowed after escalation: CPU oracle decides
                ctx.route_oracle(
                    hist_idx, plan.overflow_engine(), unresolved
                )
            elif ok[row]:
                ctx.assign(hist_idx, {
                    "valid?": True,
                    "engine": "tpu",
                    "kernel": plan.kernel,
                })
            else:
                ctx.assign(hist_idx, {
                    "valid?": False,
                    "engine": "tpu",
                    "kernel": plan.kernel,
                    "failed-event": int(failed_at[row]),
                })

    # -- dispatch path ----------------------------------------------------

    def _dispatch_chunk(self, plan, arrays, rows):
        """Queue one footprint-safe chunk on the device (async);
        ``arrays`` is already padded to the stable dispatch shape (a
        device multiple under a mesh)."""
        from ..ops import wgl

        chunk_id = self._next_chunk
        self._next_chunk += 1
        n_dev = self.n_devices
        B_pad = arrays[0].shape[0]
        n_live = len(rows)
        # under a mesh the executable is the shard_map wrapper: jit
        # traces the PER-SHARD shape, so the compile/execute phase
        # split keys on (fn, per-shard rows, mesh width) — a
        # single-device claim at the same global rows is a different
        # executable and must not mask a mesh compile (or vice versa)
        disp_shape = B_pad if n_dev == 1 else (B_pad // n_dev, n_dev)
        first = wgl._claim_shape(plan.fn, disp_shape)
        phase = "compile" if first else "execute"
        self.phase_counts[phase] += 1
        if first and self.on_cold_compile is not None:
            try:
                self.on_cold_compile(plan, arrays, disp_shape)
            except Exception:  # noqa: BLE001 — cache bookkeeping only
                pass
        # per-chip budget accounting: shard_map splits the chunk's rows
        # evenly, so each chip holds B_pad/n of them while the dispatch
        # is in flight.  Keyed on the plan's shape facts INCLUDING the
        # effective cap — not id(fn): an lru-evicted fn's id can be
        # reused by a new compile (corrupting a resident daemon's
        # accounting), and the daemon re-points max_dispatch per
        # request group, so the same kernel at a different cap must be
        # a different ledger entry, never a stale-cap false breach.
        chip_rows = -(-B_pad // n_dev)
        fnk = (plan.kernel, plan.E, plan.C, plan.frontier, plan.disp)
        acct = self.chip_row_accounting.setdefault(
            fnk, {"kernel": plan.kernel, "peak_chip_rows": 0,
                  "chip_cap": plan.disp},
        )
        # shard padding + device balance: pads sit at the tail, so the
        # last shards absorb them — the occupancy gauge makes chronic
        # imbalance (pad-heavy tails on every dispatch) visible
        if obs.enabled():
            obs.count(
                "jepsen_kernel_dispatches_total", 1,
                engine=plan.kernel, phase=phase,
            )
            if B_pad > n_live:
                obs.count(
                    "jepsen_engine_shard_pad_rows_total", B_pad - n_live,
                )
        shard = B_pad // n_dev
        for d in range(n_dev):
            self._dev_rows_total[d] += shard
            self._dev_rows_live[d] += min(max(n_live - d * shard, 0), shard)
        self._chunks[chunk_id] = {
            "plan": plan, "arrays": arrays, "rows": rows,
            "n": n_live, "phase": phase, "chip_rows": chip_rows,
            "acct_key": fnk,
        }

        # plans may carry their own dispatch lowering (the Elle screen
        # plans shard a single relation-matrix input; history plans
        # keep the 6-array sharded_check path)
        run_rows = getattr(plan, "run_rows", None)

        def thunk():
            # the in-flight increment lives INSIDE the thunk: submit
            # retires older entries (decrementing them via settle)
            # BEFORE dispatching, so counting earlier would overstate
            # the peak by one retired chunk.  Runs synchronously on
            # the owner thread, like everything the window calls.
            cur = self._chip_rows_inflight.get(fnk, 0) + chip_rows
            self._chip_rows_inflight[fnk] = cur
            if cur > acct["peak_chip_rows"]:
                acct["peak_chip_rows"] = cur
            if run_rows is not None:
                return run_rows(self.mesh, arrays)
            return wgl._run_rows(plan.fn, self.mesh, arrays)

        self._win.submit(
            chunk_id,
            thunk,
            attrs={"engine": plan.kernel, "rows": n_live,
                   "phase": phase},
        )

    def submit(self, pb) -> None:
        """Dispatch one planned bucket in footprint-safe chunks through
        the window (or settle it inline when no kernel can run)."""
        from ..ops import wgl

        plan, arrays, rows = pb.plan, pb.arrays, pb.rows
        B = arrays[0].shape[0]
        if plan.fn is None or plan.disp == 0:
            # no dispatchable kernel (oracle-routed shape, a dense-only
            # spec outside its envelope, or even one row would crash
            # the worker): every escalation rung is equally
            # undispatchable (caps shrink with capacity), so settling
            # INLINE is dispatch-free — and it hands the bucket's rows
            # to the oracle pool NOW, overlapping the remaining device
            # work instead of waiting for the window to drain
            ok = np.zeros((B,), bool)
            failed_at = np.zeros((B,), np.int32)
            overflow = np.ones((B,), bool)
            self._settle_rows(plan, arrays, rows, ok, failed_at, overflow)
            return
        # the frontier footprint budget (fn.safe_dispatch ←
        # FRONTIER_DISPATCH_BUDGET) is crash-calibrated for ONE
        # in-flight dispatch ON ONE CHIP; a window of W holds W
        # dispatches' HBM concurrently, so each frontier chunk gets
        # 1/W of the safe rows — total in-flight stays at the
        # calibrated bound.  When even that floors out (disp < W:
        # per-row footprint near the whole budget), the bucket
        # dispatches strictly serially at the full single-dispatch cap
        # instead — W one-row dispatches in flight would still
        # overshoot the bound.  Dense chunks keep the full cap: the
        # kernel is overflow-free with a small per-row footprint, and
        # multi-in-flight dense dispatch IS the measured flagship
        # bench pattern (B=16384 × window, on-chip).
        #
        # On a mesh every cap is PER CHIP: shard_map splits a chunk's
        # rows evenly across n devices, so the global chunk cap is
        # n × the per-chip cap — each chip holds exactly the rows the
        # single-chip calibration allows, never a share of a global
        # pool another chip could have drained.
        n_dev = self.n_devices
        # plans that carry their own arrays (the Elle screens' single
        # relation matrix) declare their own neutral pad fills; the
        # history kernels keep the shared 6-array convention
        pad_fills = getattr(plan, "pad_fills", wgl._PAD_FILLS)
        per_chip = plan.disp
        serialize = False
        if plan.kernel != "dense" and self._win.window > 1:
            if per_chip >= self._win.window:
                per_chip = per_chip // self._win.window
            else:
                serialize = True
        chunk_cap = per_chip * n_dev
        from ..parallel import mesh as mesh_mod

        if B <= chunk_cap:
            # stable-shape dispatch: round the PER-SHARD row count up
            # to its power-of-two bucket (shard_row_target; capped at
            # the footprint-safe chunk cap, itself a device multiple)
            # with neutral all-padding rows — settle slices the
            # outputs back to the live rows, so verdicts are untouched
            # while repeat traffic reuses one executable per bucket
            # and non-divisible batches shard cleanly
            target = min(chunk_cap, shard_row_target(B, n_dev))
            if target > B:
                arrays = tuple(
                    mesh_mod.pad_to_multiple(np.asarray(a), target, fill)
                    for a, fill in zip(arrays, pad_fills)
                )
            if serialize:
                self._win.drain()
            self._dispatch_chunk(plan, arrays, rows)
            if serialize:
                self._win.drain()
            return

        for lo in range(0, B, chunk_cap):
            hi = min(lo + chunk_cap, B)
            # every chunk (including the tail, padded with neutral
            # all-padding rows) dispatches at the same cap-row shape:
            # one executable, never a per-tail-size compile — and the
            # cap is a device multiple, so every chunk shards evenly
            chunk = tuple(
                mesh_mod.pad_to_multiple(
                    np.asarray(a[lo:hi]), chunk_cap, fill
                )
                for a, fill in zip(arrays, pad_fills)
            )
            if serialize:
                self._win.drain()
            self._dispatch_chunk(plan, chunk, rows[lo:hi])
        if serialize:
            self._win.drain()

    def reset(self) -> int:
        """Discard all transient dispatch state — in-flight window
        entries (unsynced, see :meth:`DispatchWindow.abandon`), the
        chunk map, parked escalations — WITHOUT assigning any verdicts.
        The resident service calls this when a batch raised: reusing
        the executor with a poisoned window would retire the failed
        batch's dispatches into the NEXT batch (re-raising its failure
        against innocent requests) and re-dispatch its parked
        escalation arrays into dead contexts.  Returns the number of
        abandoned dispatches."""
        n = self._win.abandon()
        self._chunks.clear()
        self._pending_escalations = []
        self._chip_rows_inflight.clear()
        return n

    def drain(self) -> None:
        """Retire every in-flight dispatch, then run the deferred
        escalation ladder with the window empty — exactly one
        in-flight dispatch, the regime the footprint budget was
        calibrated in (and the serial path's order).  Parked chunks
        merge per plan first (live rows only — tail chunks carry
        neutral padding rows that must not interleave), so a bucket
        pays ONE padded rerun per escalation rung like the serial
        batch-wide pass did, not one ladder per chunk."""
        self._win.drain()
        if obs.enabled() and self.mesh is not None:
            # per-device occupancy: the live (non-padding) share of the
            # rows each chip was handed across this executor's
            # dispatches.  Pads sit at the shard tail, so a chronically
            # pad-heavy last device reads as low occupancy here — the
            # shard-balance diagnostic for non-divisible traffic.
            for d, total in enumerate(self._dev_rows_total):
                if total:
                    obs.gauge_set(
                        "jepsen_engine_device_occupancy_ratio",
                        self._dev_rows_live[d] / total,
                        device=str(d),
                    )
        pending = self._pending_escalations
        self._pending_escalations = []
        merged: Dict[int, list] = {}
        merged_order: List[int] = []
        for item in pending:
            pid = id(item[0])
            if pid not in merged:
                merged[pid] = []
                merged_order.append(pid)
            merged[pid].append(item)
        for pid in merged_order:
            group = merged[pid]
            if len(group) == 1:
                self._settle_rows(*group[0])
                continue
            plan = group[0][0]
            arrays = tuple(
                np.concatenate(
                    [np.asarray(g[1][i][: len(g[2])]) for g in group]
                )
                for i in range(6)
            )
            rows = [r for g in group for r in g[2]]
            self._settle_rows(
                plan, arrays, rows,
                np.concatenate([g[3] for g in group]),
                np.concatenate([g[4] for g in group]),
                np.concatenate([g[5] for g in group]),
            )
