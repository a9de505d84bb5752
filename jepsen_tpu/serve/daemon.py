"""The resident checker daemon: one process owns the device, the
compiled-kernel cache, and the oracle worker pool; many client runs
share them.

Why a daemon: every ``cli test`` run pays backend init and per-shape
re-jit from scratch, and init alone can eat the accelerator win.  Keeping the mesh and jit cache resident
amortizes both across runs and users; the ``check(...)`` seam stays
the client API (jepsen_tpu.serve.client), so tests don't change.

Architecture (doc/checker-service.md):

- **Request handlers** (one HTTP thread per client, stdlib
  ``ThreadingHTTPServer``) do the *pure planning half*: decode the
  batch, run the P-compositionality front-end
  (:class:`~jepsen_tpu.engine.decompose.DecomposedRun` — partitionable
  histories split into per-partition sub-histories right here), and
  encode each stream into raw shape buckets
  (:meth:`~jepsen_tpu.engine.planning.Planner.encode_buckets`) — all
  parallel-safe host work.  Unencodable histories hit the shared
  oracle pool immediately, before the request even queues.
- **The device thread** owns the *execution half*: ONE resident
  :class:`~jepsen_tpu.engine.execution.Executor` (created on this
  thread — the dispatch window is owner-thread confined).  It pops
  whole admission-queue backlogs, groups compatible requests (same
  wire model + planning opts), **coalesces same-(E, C) buckets across
  runs** (:func:`~jepsen_tpu.engine.planning.merge_buckets`) into
  shared dispatch chunks, and signals each request's ``device_done``
  event when its rows have settled.  Per-row ``(ctx, idx)`` tokens
  route every verdict back to its own client.
- **Backpressure**: admission is bounded by queued request count AND
  queued history rows; past either bound ``/check`` answers 503 and
  the client falls back to its in-process engine.  In-flight HBM
  needs no extra policy — the shared executor inherits the
  footprint-safe chunk caps (frontier chunks take 1/window of
  ``fn.safe_dispatch``), so coalesced load can never hold more
  concurrent HBM than the crash-calibrated single-dispatch budget.
- **Coalescing is backpressure-driven**: a lone request dispatches
  immediately (zero added latency); requests arriving while the
  device is busy pile up in the queue and merge into the next device
  batch.  ``JEPSEN_TPU_SERVE_COALESCE_WAIT`` adds a bounded gather
  window for deterministic coalescing in tests/smoke.

Shutdown drains: ``POST /shutdown`` stops admission, the device
thread finishes every queued request, handlers flush their responses,
then the HTTP server stops.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from .. import obs
from ..engine import decompose, execution, planning
from ..obs import drift as obs_drift
from ..obs import journal as obs_journal
from ..obs import profiling as obs_profiling
from ..obs import propagate
from . import protocol

#: admission bounds: queued (not yet device-processed) requests and
#: histories; past either, /check answers 503 "backlogged" and the
#: client falls back in-process
DEFAULT_MAX_QUEUE_RUNS = 8
DEFAULT_MAX_QUEUE_ROWS = 65536

#: how long a handler waits for the device thread before answering 500
DEFAULT_REQUEST_TIMEOUT_S = 600.0


#: idle-window WAL auto-compaction threshold: past this size the
#: device thread's housekeeping turn rewrites the verdict WAL down to
#: the rows still replayable (JEPSEN_TPU_WAL_COMPACT_BYTES overrides;
#: 0 disables)
DEFAULT_WAL_COMPACT_BYTES = 32 * 1024 * 1024


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class _Stream:
    """One planning stream of a request: a tag (``"main"`` for
    pass-through histories under the wire model, ``"sub"`` for the
    decomposition front-end's per-partition sub-histories), the
    representative (model, spec) that plans it, and its raw encoded
    buckets.  Same-tag streams from a compatible group merge across
    runs — decomposed sub-histories coalesce into shared dispatches
    exactly like whole histories do."""

    __slots__ = ("tag", "model", "spec", "buckets", "order")

    def __init__(self, tag, model, spec, buckets, order):
        self.tag = tag
        self.model = model
        self.spec = spec
        self.buckets = buckets
        self.order = order


class _NoOracles:
    """Oracle-interface stub for requests that never submit oracle
    work (the Elle screen requests): the batch-failure and abandon
    paths can treat every queued request uniformly."""

    def abandon_oracles(self) -> int:
        return 0


_NO_ORACLES = _NoOracles()


class _ElleRequest:
    """One admitted /elle screen batch: encoded relation-bit graphs
    whose (vertex bucket, filter profile) buckets COALESCE ACROSS
    REQUESTS inside ``ops.cycles.screen_graphs`` — Elle traffic from
    concurrent runs shares dispatch rows through the resident
    executor exactly like history buckets do."""

    kind = "elle"
    __slots__ = ("graphs", "rows", "n", "t_admitted", "device_done",
                 "error", "diag", "abandoned", "results", "run",
                 "trace_id")

    def __init__(self, graphs, trace_id: Optional[str] = None):
        self.graphs = graphs
        self.rows = self.n = len(graphs)
        self.t_admitted = time.perf_counter()
        self.device_done = threading.Event()
        self.error: Optional[str] = None
        self.diag: dict = {}
        self.abandoned = False
        self.results: Optional[list] = None
        self.run = _NO_ORACLES
        #: caller's trace id (obs.propagate) — tags this request's
        #: daemon-side spans + journal rows; None when untraced
        self.trace_id = trace_id


class _Request:
    """One admitted /check batch, in flight between a handler thread
    and the device thread.  Handler-side state is written before the
    queue put; device-side results are read only after ``device_done``
    (the Event provides the happens-before edge).  ``run`` is the
    batch's :class:`~jepsen_tpu.engine.decompose.DecomposedRun` —
    result routing, oracle hand-off, and the AND-at-settle merge all
    live there; ``streams`` carry its encoded buckets."""

    kind = "check"
    __slots__ = ("run", "streams", "group_key", "model",
                 "plan_opts", "exec_opts", "n", "rows", "t_admitted",
                 "device_done", "error", "diag", "abandoned", "trace_id",
                 "replayed")

    def __init__(self, run, streams, group_key, model, plan_opts,
                 exec_opts, n, trace_id: Optional[str] = None):
        self.run = run
        self.streams = streams
        #: client-visible batch size vs rows actually queued for the
        #: device thread: decomposition multiplies encoded rows by the
        #: partition fanout, and the row-budget backpressure must see
        #: the REAL queue footprint, not the parent count
        self.rows = sum(len(ctx.histories) for _t, ctx in run.streams())
        self.group_key = group_key
        self.model = model
        self.plan_opts = plan_opts
        self.exec_opts = exec_opts
        self.n = n
        self.t_admitted = time.perf_counter()
        self.device_done = threading.Event()
        self.error: Optional[str] = None
        self.diag: dict = {}
        #: handler gave up (refused post-planning, or timed out): the
        #: device thread must skip it and nobody drains its oracles
        self.abandoned = False
        #: caller's trace id (obs.propagate); None when untraced
        self.trace_id = trace_id
        #: result slots pre-filled from the verdict WAL (a retried
        #: request after a daemon restart) — surfaced in the diag
        self.replayed = 0


class _FeedDelta(_Request):
    """One admitted ``/feed`` delta: a :class:`_Request` whose streams
    carry ONLY the rows the delta's ``DecomposedRun.extend`` just
    created (``rows`` is overridden to the delta row count — the row
    budget must see the queued footprint, not the whole session), and
    whose ``kind`` keeps feed traffic out of the /check request
    counters."""

    kind = "feed"
    __slots__ = ()


class _FeedSession:
    """One open streaming-ingest session (``POST /feed``): the
    session's :class:`~jepsen_tpu.engine.decompose.DecomposedRun`
    grows by ``extend`` per delta.  ``lock`` serializes deltas — the
    run's planning/execution phase contract allows exactly one delta
    in flight per session (concurrent appends to one session would
    race the device thread's result assignment)."""

    __slots__ = ("sid", "run", "model", "plan_opts", "exec_opts",
                 "group_key", "trace_id", "lock", "last_seq", "ops",
                 "history_idx", "probe_idx", "probed_n", "prior",
                 "t_open")

    def __init__(self, sid, run, model, plan_opts, exec_opts,
                 group_key, trace_id, prior):
        self.sid = sid
        self.run = run
        self.model = model
        self.plan_opts = plan_opts
        self.exec_opts = exec_opts
        self.group_key = group_key
        self.trace_id = trace_id
        self.lock = threading.Lock()
        #: highest ingested delta seq — a retried append (same seq,
        #: response lost on the wire) acks without re-dispatching
        self.last_seq = -1
        #: op-mode accumulator: raw completed-op event dicts in
        #: shipped (real-time) order; probes check the assembled
        #: prefix history as it grows
        self.ops: List[dict] = []
        #: run indices of client-fed whole histories, in feed order —
        #: what close() returns results for
        self.history_idx: List[int] = []
        #: run index + coverage of the latest op-prefix probe (close
        #: reuses it as the final verdict when no ops arrived since)
        self.probe_idx: Optional[int] = None
        self.probed_n = 0
        #: WAL rows a previous daemon life settled under this session
        #: id — replayed into each delta's fresh slots
        self.prior = prior
        self.t_open = time.time()


class AdmissionState:
    """The routing/admission half of a daemon, split from device
    ownership (ROADMAP item 1): the bounded queue and row budget, the
    idempotent-retry response cache, quarantined routes, open feed
    sessions, watcher accounting, and the stop flag — everything a
    request touches BEFORE the device thread owns it, behind ONE
    condition.  Device ownership (executor, mesh, jit cache) lives on
    :class:`CheckerDaemon`'s device thread; nothing here reaches for
    process-global device state, which is exactly why N daemons per
    host (``--supervise --fleet N``) are just N ``(AdmissionState,
    executor)`` pairs on distinct ports/WALs/journals."""

    def __init__(self, max_queue_runs: int, max_queue_rows: int):
        self.max_queue_runs = max_queue_runs
        self.max_queue_rows = max_queue_rows
        #: ONE condition guards every piece of handler/device shared
        #: state (queue, row budget, stats) — and doubles as the
        #: device thread's wake-up signal
        self._wake = threading.Condition()
        self._stopping = threading.Event()
        self._queue: List[_Request] = []  # jt: guarded-by(_wake)
        self._queued_rows = 0  # jt: guarded-by(_wake)
        self._in_flight = 0  # jt: guarded-by(_wake)
        self.stats = {  # jt: guarded-by(_wake)
            "requests": 0, "histories": 0, "rejected": 0,
            "coalesced": 0, "batches": 0, "warm_dispatches": 0,
            "cold_dispatches": 0, "errors": 0,
            "elle_requests": 0, "elle_graphs": 0,
            "quarantined_rows": 0, "replayed": 0, "deduped": 0,
            "feed_sessions": 0, "feed_deltas": 0, "feed_histories": 0,
            "watch_events": 0, "wal_compactions": 0,
        }
        #: open streaming-ingest sessions by session id
        self._feeds: Dict[str, _FeedSession] = {}  # jt: guarded-by(_wake)
        #: live /watch subscribers (SSE handler threads)
        self._watchers = 0  # jt: guarded-by(_wake)
        #: completed-response cache for idempotent retries: a client
        #: retry (same request id) of an ALREADY-ANSWERED request is
        #: served from here without touching the device or the
        #: counters — retried work is never double-counted
        self._done: "OrderedDict[str, Tuple[int, dict]]" = OrderedDict()  # jt: guarded-by(_wake)
        self._done_cap = 128
        #: quarantined (kernel, E, C) routes: a device fault on one
        #: route degrades THAT route to the CPU oracle instead of
        #: failing whole batches (graceful degradation); values are
        #: the triggering error repr
        self._quarantine: Dict[Tuple, str] = {}  # jt: guarded-by(_wake)

    # -- admission (handler threads) --------------------------------------

    def precheck(self, n_rows: int) -> bool:
        """Cheap capacity check BEFORE the planning half: a request
        that would be refused must not pay decode+encode (nor submit
        oracle searches the pool would burn for nobody) just to hear
        503.  The authoritative check is :meth:`admit` — this one only
        sheds the obvious overload early, so the race window between
        the two is a single in-flight planning pass, not the whole
        backlog.  ``n_rows`` here is the parent history count (the
        decomposition fanout is unknowable pre-planning); admit()
        re-checks against the real post-decomposition row count."""
        with self._wake:
            return not (
                self._stopping.is_set()
                or len(self._queue) >= self.max_queue_runs
                or self._queued_rows + n_rows > self.max_queue_rows
            )

    def admit(self, req: _Request) -> bool:
        with self._wake:
            if self._stopping.is_set():
                return False
            # the authoritative row budget counts req.rows — the
            # encoded rows actually queued (decomposition fans a
            # parent history out into per-partition sub-rows; see
            # _Request.rows) — while precheck's pre-planning
            # estimate can only see the parent count
            if (len(self._queue) >= self.max_queue_runs
                    or self._queued_rows + req.rows > self.max_queue_rows):
                self.stats["rejected"] += 1
                obs.count("jepsen_serve_rejected_total")
                return False
            self._queue.append(req)
            self._queued_rows += req.rows
            if req.kind == "elle":
                # graphs are not histories: the /check throughput
                # accounting must not inflate from screen traffic
                self.stats["elle_requests"] += 1
                self.stats["elle_graphs"] += req.n
                obs.count("jepsen_serve_elle_requests_total")
                obs.count("jepsen_serve_elle_graphs_total", req.n)
            elif req.kind == "feed":
                # feed deltas count under jepsen_feed_* at ingest
                # completion (_feed_dispatch), not here: a delta is
                # not a /check request and must not inflate its stats
                pass
            else:
                self.stats["requests"] += 1
                self.stats["histories"] += req.n
                obs.count("jepsen_serve_requests_total")
                obs.count("jepsen_serve_histories_total", req.n)
            obs.gauge_set("jepsen_serve_queue_depth", len(self._queue))
            self._wake.notify()
            return True

    # -- the device-thread side -------------------------------------------

    def take_batch(self, coalesce_wait_s: float) -> List[_Request]:
        """Pop the whole current backlog (the coalescing unit), waiting
        up to ``coalesce_wait_s`` after the first arrival for company."""
        with self._wake:
            idle_waits = 0
            while not self._queue:
                if self._stopping.is_set():
                    return []
                self._wake.wait(timeout=0.2)
                idle_waits += 1
                if not self._queue and idle_waits >= 5:
                    # ~1 s with no admissions: hand the device loop a
                    # housekeeping turn (WAL auto-compaction) instead
                    # of camping on the condition forever
                    return []
            if coalesce_wait_s > 0:
                deadline = time.monotonic() + coalesce_wait_s
                while (len(self._queue) < self.max_queue_runs
                       and not self._stopping.is_set()):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._wake.wait(timeout=remaining)
            batch = self._queue
            self._queue = []
            self._queued_rows = 0
            self._in_flight = len(batch)
            obs.gauge_set("jepsen_serve_queue_depth", 0)
            return batch

    def drain_queue(self) -> List[_Request]:
        """Take everything still queued (the device-thread-failed
        path): the caller fails each request itself."""
        with self._wake:
            queued, self._queue = self._queue, []
            self._queued_rows = 0
            return queued

    # -- idempotent retries (handler threads) ------------------------------

    def dedup_hit(self, req_id) -> Optional[Tuple[int, dict]]:
        """Serve a retried request id from the completed-response
        cache: a client retry of an ALREADY-ANSWERED request (the
        response was lost on the wire, not the work) is answered from
        here without touching the device or inflating the request
        counters — retried work is never double-counted."""
        if not req_id:
            return None
        with self._wake:
            hit = self._done.get(req_id)
            if hit is None:
                return None
            self._done.move_to_end(req_id)
            self.stats["deduped"] += 1
        obs.count("jepsen_serve_request_dedup_total")
        return hit

    def dedup_store(self, req_id, code: int, payload: dict) -> None:
        if not req_id or code != 200:
            # only durable successes are idempotent-replayable; a
            # retried failure should retry the actual work
            return
        with self._wake:
            self._done[req_id] = (code, payload)
            self._done.move_to_end(req_id)
            while len(self._done) > self._done_cap:
                self._done.popitem(last=False)

    # -- graceful degradation (device thread) ------------------------------

    def mark_quarantined(self, routes, err) -> int:
        """Record device-faulted (kernel, E, C) routes: subsequent
        buckets on a quarantined route go straight to the CPU oracle
        instead of re-hitting the faulty compile/dispatch — one bad
        route degrades, the daemon and every other route keep serving
        (doc/checker-service.md "Failure modes & recovery")."""
        with self._wake:
            fresh = [r for r in routes if r not in self._quarantine]
            for r in fresh:
                self._quarantine[r] = repr(err)
            n_q = len(self._quarantine)
        if fresh:
            obs.count("jepsen_serve_quarantine_total", len(fresh))
            obs.gauge_set("jepsen_serve_quarantined_routes", n_q)
        return len(fresh)


class CheckerDaemon:
    """The resident service.  ``start(block=False)`` returns once the
    device thread is ready; ``port`` then holds the bound port (useful
    with port=0 in tests)."""

    def __init__(
        self,
        host: str = protocol.DEFAULT_HOST,
        port: int = protocol.DEFAULT_PORT,
        *,
        window: Optional[int] = None,
        mesh=None,
        max_queue_runs: Optional[int] = None,
        max_queue_rows: Optional[int] = None,
        coalesce_wait_s: Optional[float] = None,
        cost_fn=None,
        journal_path: Optional[str] = None,
        journal_max_bytes: int = obs_journal.DEFAULT_MAX_BYTES,
        wal_path: Optional[str] = None,
        wal_compact_bytes: Optional[int] = None,
        drift: bool = True,
        drift_threshold: Optional[float] = None,
        profile_dir: str = "profiles",
        aot_cache_dir: Optional[str] = None,
    ):
        #: per-bucket device-cost estimator driving largest-first
        #: dispatch of coalesced work.  The default is the
        #: calibration-aware planning.estimated_cost: with a tuned
        #: artifact active (doc/tuning.md) it serves the MEASURED
        #: per-(kernel, E, C, F, rows) cost table, else the analytic
        #: proxy — pass cost_fn= to override either
        self.cost_fn = cost_fn or planning.estimated_cost
        self.host = host
        self.port = port
        self.window = window
        self.mesh = mesh
        # `is None`, not truthiness: --max-queue 0 means "refuse all
        # new work", which must not silently become the default bound
        max_runs = (
            int(os.environ.get("JEPSEN_TPU_SERVE_MAX_QUEUE",
                               DEFAULT_MAX_QUEUE_RUNS))
            if max_queue_runs is None else max_queue_runs
        )
        max_rows = (
            DEFAULT_MAX_QUEUE_ROWS if max_queue_rows is None
            else max_queue_rows
        )
        #: the routing/admission half (ROADMAP item 1 split): queue,
        #: budgets, retry cache, quarantine, feed/watch registries —
        #: everything shared between handler threads and the device
        #: thread.  Device ownership stays below on the device thread.
        self.admission = AdmissionState(max_runs, max_rows)
        self.coalesce_wait_s = (
            coalesce_wait_s
            if coalesce_wait_s is not None
            else _env_float("JEPSEN_TPU_SERVE_COALESCE_WAIT", 0.0)
        )
        #: dispatch-journal destination (obs.journal): None = off — the
        #: constructor default, so in-process/test daemons never write
        #: to cwd by accident; the `serve()` CLI entry defaults it ON
        self.journal_path = journal_path
        self.journal_max_bytes = journal_max_bytes
        #: cost-model drift sentinel (obs.drift): rides the journal
        #: stream, so it only arms when the journal is on; `drift=False`
        #: (or falsy JEPSEN_TPU_DRIFT at the `serve()` entry) disables
        self.drift = drift
        self.drift_threshold = drift_threshold
        #: where `POST /profile` captures land when the request names
        #: no directory (each capture gets a timestamped subdir)
        self.profile_dir = profile_dir
        #: verdict-WAL destination (obs.journal.VerdictWAL): None = off
        #: (constructor default, like the dispatch journal); the
        #: `serve()` entry wires it from JEPSEN_TPU_WAL.  On a fresh
        #: start the existing file becomes the replay index — a
        #: restarted daemon re-dispatches only unsettled partitions of
        #: retried requests (doc/checker-service.md "Failure modes &
        #: recovery")
        self.wal_path = wal_path
        self._wal: Optional[obs_journal.VerdictWAL] = None
        self._wal_replay: Dict[str, dict] = {}
        #: idle-window auto-compaction threshold (bytes; 0 disables) —
        #: the device thread's housekeeping turn checks it
        self.wal_compact_bytes = (
            _env_int("JEPSEN_TPU_WAL_COMPACT_BYTES",
                     DEFAULT_WAL_COMPACT_BYTES)
            if wal_compact_bytes is None else wal_compact_bytes
        )
        #: shared on-disk AOT executable cache (serve.aotcache): the
        #: device thread records every cold compile here and pre-warms
        #: matching entries at startup, so a supervisor-restarted
        #: daemon's first request runs with zero cold dispatches.
        #: None = off (constructor default, like the journal/WAL); the
        #: `serve()` entry wires it from JEPSEN_TPU_SERVE_AOT_CACHE
        self.aot_cache_dir = aot_cache_dir
        self._aot_warmed = 0
        self._aot_matched = 0
        self._aot_recorder = None
        self.t_start = time.time()
        self._server: Optional[ThreadingHTTPServer] = None
        self._device_thread: Optional[threading.Thread] = None
        self._ready = threading.Event()
        self._platform: Optional[str] = None
        self._fatal: Optional[str] = None
        #: devices the resident executor shards across (set by the
        #: device thread once the executor exists; None = not ready)
        self._n_devices: Optional[int] = None

    # -- AdmissionState delegation ------------------------------------------
    # The handler/device code below predates the split and still says
    # `self._wake` / `self._queue` / `self.stats`; these forwarders keep
    # that surface (and the public ctor/status contract) stable while
    # the state itself lives on `self.admission`.

    @property
    def _wake(self):
        return self.admission._wake

    @property
    def _stopping(self):
        return self.admission._stopping

    @property
    def stats(self):
        return self.admission.stats

    @property
    def max_queue_runs(self) -> int:
        return self.admission.max_queue_runs

    @property
    def max_queue_rows(self) -> int:
        return self.admission.max_queue_rows

    @property
    def _queue(self):
        return self.admission._queue

    @property
    def _queued_rows(self) -> int:
        return self.admission._queued_rows

    @property
    def _in_flight(self) -> int:
        return self.admission._in_flight

    @_in_flight.setter
    def _in_flight(self, v: int) -> None:
        self.admission._in_flight = v

    @property
    def _watchers(self) -> int:
        return self.admission._watchers

    @_watchers.setter
    def _watchers(self, v: int) -> None:
        self.admission._watchers = v

    @property
    def _done(self):
        return self.admission._done

    @property
    def _feeds(self):
        return self.admission._feeds

    @property
    def _quarantine(self):
        return self.admission._quarantine

    # -- admission (handler threads) ---------------------------------------

    def precheck_admit(self, n_rows: int) -> bool:
        return self.admission.precheck(n_rows)

    def admit(self, req: _Request) -> bool:
        return self.admission.admit(req)

    # -- the device thread ---------------------------------------------------

    def _take_batch(self) -> List[_Request]:
        return self.admission.take_batch(self.coalesce_wait_s)

    def _device_loop(self) -> None:  # jt: thread-entry
        """The resident execution half: owns the device, the dispatch
        window, and the jit cache for the daemon's whole life."""
        try:
            import jax

            # the assignments below are published to handler threads
            # by `_ready.set()` / `start()`'s `_ready.wait()` — no
            # handler can observe them mid-write
            self._platform = jax.devices()[0].platform  # jt: allow[concurrency-unguarded-shared] — published via _ready (see above)
            # created HERE: the dispatch window is owner-thread
            # confined to the device thread
            executor = execution.Executor(self.window, mesh=self.mesh)
            # the executor auto-resolves a slice mesh when none was
            # passed (parallel.mesh.engine_default_mesh); adopt the
            # RESOLVED mesh so /status advertises what actually runs
            # and mesh-matched client requests can be serviced
            self.mesh = executor.mesh  # jt: allow[concurrency-unguarded-shared] — published via _ready
            self._n_devices = executor.n_devices  # jt: allow[concurrency-unguarded-shared] — published via _ready
            if self.aot_cache_dir:
                # cold-start elimination: replay the shared manifest ON
                # this thread, BEFORE /healthz goes ready — a restarted
                # daemon's first request then runs with zero cold
                # dispatches — and hook the recorder so every cold
                # compile this life pays is warm next life (fleet-wide:
                # the manifest and the XLA cache under it are shared)
                from . import aotcache

                try:
                    warmed, matched = aotcache.warm(executor,
                                                    self.aot_cache_dir)
                    self._aot_warmed = warmed  # jt: allow[concurrency-unguarded-shared] — published via _ready
                    self._aot_matched = matched  # jt: allow[concurrency-unguarded-shared] — published via _ready
                    self._aot_recorder = aotcache.Recorder(  # jt: allow[concurrency-unguarded-shared] — published via _ready
                        self.aot_cache_dir,
                        list(self.mesh.devices.shape)
                        if self.mesh is not None else [1],
                    )
                    executor.on_cold_compile = self._aot_recorder
                except Exception:  # noqa: BLE001 — the cache is an
                    # optimization: a damaged dir means a cold start,
                    # never a dead daemon
                    executor.reset()
        except Exception as e:  # noqa: BLE001 — surface via /healthz + 500s
            self._fatal = repr(e)  # jt: allow[concurrency-unguarded-shared] — published via _ready
            self._ready.set()
            self._fail_all_queued()
            return
        self._ready.set()
        while True:
            batch = self._take_batch()
            if not batch:
                if self._stopping.is_set():
                    return  # drained: every admitted request settled
                self._maybe_compact_wal()
                continue
            try:
                self._process_batch(executor, batch)
                with self._wake:
                    self._in_flight = 0
            except Exception as e:  # noqa: BLE001 — one bad batch must
                # not kill the daemon; its unsettled requests answer 500
                # (requests whose group already settled keep their
                # results — their handlers may have responded).  The
                # resident executor's transient state is discarded:
                # carrying the failed batch's in-flight dispatches or
                # parked escalations forward would poison the NEXT
                # batch (see Executor.reset)
                executor.reset()
                n_err = 0
                for req in batch:
                    if not req.device_done.is_set():
                        req.error = repr(e)
                        # the 500'd client re-runs in-process; cancel
                        # its queued oracle searches instead of letting
                        # them burn the shared pool for nobody
                        req.run.abandon_oracles()
                        req.device_done.set()
                        n_err += 1
                with self._wake:
                    self.stats["errors"] += n_err
                    self._in_flight = 0

    def _maybe_compact_wal(self) -> None:
        """Idle-window WAL auto-compaction (device thread only): past
        :attr:`wal_compact_bytes` the verdict WAL rewrites down to the
        rows still replayable — the request ids in the completed-
        response cache plus every open feed session.  ``compact()``
        swaps via ``.tmp`` + ``os.replace`` under the WAL's own lock,
        so concurrent handler appends stay safe and a kill -9
        mid-compaction leaves the original file intact (the chaos
        harness pins this); ``/watch`` followers detect the rewrite
        (WalTail's inode/size check) and restart from offset 0 —
        re-delivery is safe because verdicts are monotone and every
        row carries its full (req, stream, idx) identity."""
        wal = self._wal
        if wal is None or self.wal_compact_bytes <= 0:
            return
        try:
            if os.path.getsize(wal.path) <= self.wal_compact_bytes:
                return
        except OSError:
            return
        with self._wake:
            keep = set(self._done) | set(self._feeds)
        try:
            wal.compact(keep_reqs=keep)
        except OSError:
            return  # disk trouble: keep serving, retry next idle turn
        with self._wake:
            self.stats["wal_compactions"] += 1
        obs.count("jepsen_serve_wal_compactions_total")

    def _fail_all_queued(self) -> None:
        for req in self.admission.drain_queue():
            req.error = f"device thread failed: {self._fatal}"
            req.device_done.set()

    def _process_batch(self, executor, batch: List[_Request]) -> None:
        """Group compatible requests, coalesce same-shape buckets across
        runs, dispatch each group through the shared window."""
        with self._wake:
            self.stats["batches"] += 1
        groups: Dict[Tuple, List[_Request]] = {}
        group_order: List[Tuple] = []
        elle_reqs: List[_ElleRequest] = []
        for req in batch:
            if req.abandoned:
                # handler gave up (timeout): skip its work and cancel
                # the oracle searches its planning already submitted —
                # safe here, the device thread is the run's only owner
                req.run.abandon_oracles()
                continue
            if isinstance(req, _ElleRequest):
                elle_reqs.append(req)
                continue
            if req.group_key not in groups:
                groups[req.group_key] = []
                group_order.append(req.group_key)
            groups[req.group_key].append(req)
        batch_attrs = {"requests": len(batch),
                       "groups": len(group_order) + bool(elle_reqs)}
        batch_ids = ",".join(sorted(
            {r.trace_id for r in batch if getattr(r, "trace_id", None)}))
        if batch_ids:
            batch_attrs[propagate.ATTR_TRACE_IDS] = batch_ids
        with obs.span("serve/batch", cat="serve", **batch_attrs):
            if elle_reqs:
                self._process_elle(executor, elle_reqs)
                for req in elle_reqs:
                    req.device_done.set()
            # plan every group first (pure host work), then dispatch
            # groups largest summed-estimated-cost first: a group's
            # cost is the SUM over its planned buckets' rows — so a
            # high-fanout decomposed request, whose parent cost lives
            # spread across many per-partition sub-buckets, schedules
            # by its real total instead of arrival order (the ROADMAP
            # items 3+4 partition-aware scheduling leftover).  The
            # stable sort keeps arrival order on ties.
            planned = {
                gkey: self._plan_group(groups[gkey]) for gkey in group_order
            }
            group_order.sort(
                key=lambda k: sum(self.cost_fn(pb) for pb in planned[k][0]),
                reverse=True,
            )
            for gkey in group_order:
                reqs = groups[gkey]
                self._dispatch_group(executor, reqs, *planned[gkey])
                for req in reqs:
                    if req.abandoned:
                        # handler timed out while this group ran: no
                        # one will drain these futures (a set() after
                        # this check races only a just-expiring wait —
                        # bounded to already-submitted futures)
                        req.run.abandon_oracles()
                    req.device_done.set()

    def _process_elle(self, executor, reqs: List[_ElleRequest]) -> None:
        """The Elle screen arm of a device batch: graphs from every
        queued /elle request screen through ONE ``screen_graphs`` pass
        over the resident executor, so same-(bucket, profile) buckets
        coalesce across runs into shared dispatches."""
        from ..ops import cycles as ops_cycles

        if len(reqs) > 1:
            obs.count("jepsen_serve_elle_coalesced_total", len(reqs))
        for req in reqs:
            # admission→dispatch: the queue-wait the /status live view
            # and item 3's admission-control signal key on
            obs.observe("jepsen_serve_queue_wait_seconds",
                        time.perf_counter() - req.t_admitted)
        attrs = {"graphs": sum(r.n for r in reqs)}
        trace_ids = ",".join(
            sorted({r.trace_id for r in reqs if r.trace_id}))
        if trace_ids:
            attrs[propagate.ATTR_TRACE_IDS] = trace_ids
        executor.journal_context = {
            "coalesced": len(reqs), "trace_id": trace_ids}
        encs = [g for req in reqs for g in req.graphs]
        with obs.span("serve/screen", cat="serve", **attrs):
            results = ops_cycles.screen_graphs(encs, executor=executor)
        lo = 0
        for req in reqs:
            req.results = results[lo:lo + req.n]
            req.diag = {
                "coalesced_with": len(reqs) - 1,
                "graphs": req.n,
                "queue_wait_s": round(
                    time.perf_counter() - req.t_admitted, 4),
            }
            lo += req.n

    def _plan_group(self, reqs: List[_Request]):
        """The pure planning half of one compatible group: merge per
        STREAM TAG — a decomposed request carries a "main"
        (pass-through, wire-model spec) and a "sub" (per-partition
        sub-model spec) stream, and only same-spec buckets may stack —
        but within a tag, buckets coalesce across every run in the
        group, so concurrent decomposed requests share dispatch rows
        exactly like whole histories do."""
        first = reqs[0]
        tags: List[str] = []
        for req in reqs:
            for st in req.streams:
                if st.tag not in tags:
                    tags.append(st.tag)
        planned = []
        n_buckets = 0
        for tag in tags:
            streams = [st for req in reqs for st in req.streams
                       if st.tag == tag]
            rep = streams[0]
            planner = planning.Planner(
                rep.model, spec=rep.spec, **first.plan_opts,
            )
            merged, order = planning.merge_buckets(
                (st.buckets, st.order) for st in streams
            )
            n_buckets += len(order)
            for key in order:
                encs, tokens = merged[key]
                pb = planner.plan_rows(key, encs, tokens)
                if pb is not None:
                    planned.append(pb)
        return planned, n_buckets

    def _dispatch_group(self, executor, reqs: List[_Request],
                        planned: list, n_buckets: int) -> None:
        first = reqs[0]
        for req in reqs:
            # admission→dispatch: the queue-wait the /status live view
            # and item 3's admission-control signal key on
            obs.observe("jepsen_serve_queue_wait_seconds",
                        time.perf_counter() - req.t_admitted)
        if len(reqs) > 1:
            # counted per COMPATIBLE group, not per backlog pop:
            # requests that merely shared a device batch but sat in
            # different groups (different model/opts) shared zero
            # dispatch rows and must not inflate the coalescing
            # evidence the serve-smoke gate keys on
            with self._wake:
                self.stats["coalesced"] += len(reqs)
            obs.count("jepsen_serve_coalesced_requests_total", len(reqs))
        # the resident executor adopts this group's execution policy;
        # groups run strictly one after another (with a drain between),
        # so the mutation never races a dispatch
        executor.escalation = first.exec_opts["escalation"]
        executor.sufficient_rung = first.exec_opts["sufficient_rung"]
        executor.max_dispatch = first.exec_opts["max_dispatch"]
        trace_ids = ",".join(
            sorted({r.trace_id for r in reqs if r.trace_id}))
        executor.journal_context = {
            "coalesced": len(reqs), "trace_id": trace_ids}
        attrs = {"requests": len(reqs), "buckets": n_buckets}
        if trace_ids:
            # a shared dispatch belongs to EVERY participating run's
            # trace: /trace?ctx= matches any member of this attr
            attrs[propagate.ATTR_TRACE_IDS] = trace_ids
        pc0 = dict(executor.phase_counts)
        # dispatch EVERY planned bucket largest-estimated-cost first
        # across both streams: big buckets keep the window occupied
        # while small ones fill the tail (ROADMAP item 4's scheduling
        # direction).  The cost fn is the daemon's pluggable seam for
        # a learned per-shape model (planning.estimated_cost docs);
        # verdicts are order-independent by the engine contract, so
        # reordering is purely a throughput decision.
        planned.sort(key=self.cost_fn, reverse=True)
        with self._wake:
            quarantined = set(self._quarantine)
        # graceful degradation: a device fault on one (kernel, E, C)
        # route quarantines THAT route to the CPU oracle — this group
        # keeps dispatching its other routes, and the batch answers
        # 200 with oracle verdicts where the device failed, instead of
        # resetting the executor and 500ing everyone (the whole-batch
        # reset in _device_loop stays as the last resort).  Routed
        # tokens are deduped so a row salvaged from the in-flight
        # window never double-submits an oracle search.
        routed = set()

        def _oracle_route(tokens) -> int:
            n = 0
            for ctx, idx in tokens:
                key = (id(ctx), idx)
                if key in routed or ctx.settled(idx):
                    continue
                routed.add(key)
                ctx.route_oracle(idx, "oracle", "quarantined")
                n += 1
            return n

        n_quarantined = 0
        with obs.span("serve/dispatch", cat="serve", **attrs):
            for pb in planned:
                route = (pb.plan.kernel, pb.plan.E, pb.plan.C)
                if route not in quarantined:
                    try:
                        executor.submit(pb)
                        continue
                    except Exception as e:  # noqa: BLE001 — degrade the route
                        self._mark_quarantined([route], e)
                        quarantined.add(route)
                        n_quarantined += _oracle_route(
                            self._salvage_executor(executor))
                n_quarantined += _oracle_route(pb.rows)
            try:
                executor.drain()
            except Exception as e:  # noqa: BLE001 — fault surfaced at sync
                # the drain exposed an in-flight fault: quarantine the
                # routes the window was still carrying and salvage
                # their rows to the oracle pool
                routes = {(ch["plan"].kernel, ch["plan"].E, ch["plan"].C)
                          for ch in executor._chunks.values()}
                routes |= {(esc[0].kernel, esc[0].E, esc[0].C)
                           for esc in executor._pending_escalations}
                self._mark_quarantined(routes or {("?", 0, 0)}, e)
                quarantined.update(routes)
                n_quarantined += _oracle_route(
                    self._salvage_executor(executor))
        if n_quarantined:
            with self._wake:
                self.stats["quarantined_rows"] += n_quarantined
        warm = executor.phase_counts["execute"] - pc0["execute"]
        cold = executor.phase_counts["compile"] - pc0["compile"]
        if warm:
            # a warm hit = a dispatch that reused an already-compiled
            # (fn, shape) — the re-jit the resident cache saves
            obs.count("jepsen_serve_warm_hits_total", warm)
        with self._wake:
            self.stats["warm_dispatches"] += warm
            self.stats["cold_dispatches"] += cold
        for req in reqs:
            req.diag = {
                "coalesced_with": len(reqs) - 1,
                "warm_dispatches": warm,
                "cold_dispatches": cold,
                "queue_wait_s": round(
                    time.perf_counter() - req.t_admitted, 4),
                "buckets": n_buckets,
                "partitions": req.run.n_partitions,
            }

    # -- status -------------------------------------------------------------

    def status(self) -> dict:
        from .. import tune

        with self._wake:
            stats = dict(self.stats)
            depth = len(self._queue)
            in_flight = self._in_flight
            quarantine = [{"route": str(k), "error": v}
                          for k, v in self._quarantine.items()]
            feed_open = len(self._feeds)
            watchers = self._watchers
        total = stats["warm_dispatches"] + stats["cold_dispatches"]
        cal = tune.active()
        reg = obs.registry()
        # the live windowed view (obs.metrics slot rings): last-60 s
        # rates + queue-wait + device-busy fraction — what `top` and
        # the web panel render, and what a cumulative counter can't say
        busy_s = (reg.window_seconds_sum("jepsen_kernel_compile_seconds")
                  + reg.window_seconds_sum("jepsen_kernel_execute_seconds"))
        qw_mean = reg.window_mean("jepsen_serve_queue_wait_seconds")
        lag_mean = reg.window_mean("jepsen_feed_ingest_lag_seconds")
        live = {
            "requests_per_s": round(
                reg.window_rate("jepsen_serve_requests_total"), 4),
            "histories_per_s": round(
                reg.window_rate("jepsen_serve_histories_total"), 4),
            "elle_graphs_per_s": round(
                reg.window_rate("jepsen_serve_elle_graphs_total"), 4),
            "dispatches_per_s": round(
                reg.window_rate("jepsen_kernel_dispatches_total"), 4),
            "queue_wait_mean_s": (
                round(qw_mean, 4) if qw_mean is not None else None),
            "device_busy_ratio": round(
                min(1.0, busy_s / 60.0), 4),
            "feed_deltas_per_s": round(
                reg.window_rate("jepsen_feed_deltas_total"), 4),
            "watch_events_per_s": round(
                reg.window_rate("jepsen_watch_events_total"), 4),
            "feed_lag_mean_s": (
                round(lag_mean, 4) if lag_mean is not None else None),
        }
        journal = obs_journal.active()
        sentinel = obs_drift.active()
        return {
            # the resident calibration (doc/tuning.md): the artifact id
            # steering this daemon's window / union-mode / cost-ordered
            # dispatch, or None when running on pinned defaults —
            # CheckerDaemon(cost_fn=...) defaults to the calibration-
            # aware planning.estimated_cost, so the tuned cost table
            # drives largest-cost-first scheduling resident-side
            "calibration": cal.calibration_id if cal is not None else None,
            "ok": self._fatal is None,
            "error": self._fatal,
            "pid": os.getpid(),
            "platform": self._platform,
            "uptime_s": round(time.time() - self.t_start, 1),
            "window": self.window or execution.default_window(),
            # the resident mesh: what slice-matched clients (serve.
            # client mesh-shape servicing) compare their request
            # against; n_devices=1 + mesh_shape=None = single-device
            "n_devices": self._n_devices,
            "mesh_shape": (
                list(self.mesh.devices.shape)
                if self.mesh is not None else None
            ),
            "queue_depth": depth,
            "in_flight": in_flight,
            "max_queue_runs": self.max_queue_runs,
            "max_queue_rows": self.max_queue_rows,
            "stopping": self._stopping.is_set(),
            "warm_hit_ratio": round(stats["warm_dispatches"] / total, 4)
            if total else None,
            "journal_path": journal.path if journal else None,
            "journal_rows": journal.written if journal else 0,
            # cost-model drift sentinel (obs.drift): per-shape EWMA
            # residuals vs the calibration/proxy estimate, the worst-
            # shape aggregate score, and the retune recommendation —
            # None when the journal (and so the sentinel) is off
            "drift": sentinel.snapshot() if sentinel is not None else None,
            # degraded (kernel, E, C) routes currently served by the
            # CPU oracle, with the device error that tripped each
            "quarantine": quarantine,
            "wal_path": self._wal.path if self._wal else None,
            "wal_rows": self._wal.written if self._wal else 0,
            # the AOT executable cache (serve.aotcache): entries warmed
            # at startup vs entries matching this daemon's fingerprint
            # + mesh, and executables recorded this life — the fleet
            # tier's zero-cold-start evidence
            "aot": ({
                "dir": self.aot_cache_dir,
                "warmed": self._aot_warmed,
                "matched": self._aot_matched,
                "recorded": (self._aot_recorder.recorded
                             if self._aot_recorder is not None else 0),
            } if self.aot_cache_dir else None),
            # the online-monitor surface: open ingest sessions and
            # live /watch subscribers (doc/checker-service.md
            # "Online checking")
            "feed_open": feed_open,
            "watch_subscribers": watchers,
            "live": live,
            **stats,
        }

    def trace_dump(self, trace_id: str) -> dict:
        """The ``GET /trace?ctx=`` payload: finished daemon spans
        belonging to one trace (tagged directly, or via the comma-
        joined trace_ids attr a coalesced dispatch carries), plus the
        clock metadata (pid, wall_origin, origin_ns) the client's
        ``obs.propagate.adopt`` needs to rebase them at export."""
        t = obs.tracer()
        spans = [d for d in (rec.to_dict() for rec in t.finished())
                 if propagate.span_matches(d, trace_id)]
        return {"spans": spans, "pid": os.getpid(),
                "wall_origin": t.wall_origin, "origin_ns": t.origin_ns}

    # -- lifecycle ------------------------------------------------------------

    def start(self, block: bool = True) -> "CheckerDaemon":
        obs.enable()  # live /metrics needs the registry recording
        if self.journal_path:
            obs_journal.configure(self.journal_path,
                                  self.journal_max_bytes)
            if self.drift:
                # warm start: a restarted daemon rescores the rows its
                # previous life journalled, so the drift view survives
                # a crash exactly like the WAL's verdicts do
                sentinel = obs_drift.configure(self.drift_threshold)
                sentinel.scan(self.journal_path)
        if self.wal_path:
            # build the replay index BEFORE the writer reopens the
            # file: every verdict a previous daemon life settled is
            # replayed into retried requests instead of re-dispatched
            self._wal_replay = obs_journal.replay_index(self.wal_path)  # jt: allow[concurrency-unguarded-shared] — written before serve/device threads start (Thread.start publication)
            self._wal = obs_journal.VerdictWAL(self.wal_path)  # jt: allow[concurrency-unguarded-shared] — written before serve/device threads start (Thread.start publication)
        handler = _make_handler(self)
        self._server = ThreadingHTTPServer((self.host, self.port), handler)  # jt: allow[concurrency-unguarded-shared] — written before serve/device threads start (Thread.start publication)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._device_thread = threading.Thread(
            target=self._device_loop, name="jepsen-serve-device",
            daemon=True,
        )
        self._device_thread.start()
        self._ready.wait()  # jt: allow[net-timeout] — own device thread signals after jit warmup; bounding startup is the supervisor's job
        if block:
            print(
                f"jepsen-tpu checker service on "
                f"http://{self.host}:{self.port}/ (pid {os.getpid()})"
            )
            try:
                self._server.serve_forever()  # jt: allow[net-timeout] — the accept loop IS the process; shutdown() ends it
            finally:
                self.stop()
        else:
            threading.Thread(
                target=self._server.serve_forever, daemon=True
            ).start()
        return self

    def request_shutdown(self) -> dict:
        """Stop admitting, let the device thread drain, then stop the
        HTTP server from a helper thread (the handler that called this
        still needs to flush its response)."""
        with self._wake:
            already = self._stopping.is_set()
            self._stopping.set()
            draining = len(self._queue)
            self._wake.notify_all()
        if not already:
            threading.Thread(target=self._finish_stop, daemon=True).start()
        return {"ok": True, "draining": draining}

    def _finish_stop(self) -> None:  # jt: thread-entry
        if self._device_thread is not None:
            self._device_thread.join(timeout=DEFAULT_REQUEST_TIMEOUT_S)
        # tiny grace so in-flight handlers (incl. the /shutdown one)
        # finish writing before the listener dies
        time.sleep(0.05)
        if self._server is not None:
            self._server.shutdown()

    def stop(self) -> None:
        """Synchronous teardown (tests): drain + stop + join."""
        self.request_shutdown()
        if self._device_thread is not None:
            self._device_thread.join(timeout=30)
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()

    # -- idempotent retries (handler threads) --------------------------------

    def _dedup_hit(self, req_id) -> Optional[Tuple[int, dict]]:
        return self.admission.dedup_hit(req_id)

    def _dedup_store(self, req_id, code: int, payload: dict) -> None:
        self.admission.dedup_store(req_id, code, payload)

    # -- graceful degradation (device thread) --------------------------------

    def _mark_quarantined(self, routes, err) -> int:
        return self.admission.mark_quarantined(routes, err)

    def _salvage_executor(self, executor) -> list:
        """Capture the in-flight chunks' row tokens, then reset the
        window: after a device fault the executor's transient state is
        poisoned (see Executor.reset), but the un-settled rows it was
        carrying can still get verdicts from the oracle pool."""
        pending = [tok for ch in executor._chunks.values()
                   for tok in ch["rows"]]
        # parked escalations carry rows too — reset() would drop them
        pending += [tok for esc in executor._pending_escalations
                    for tok in esc[2]]
        executor.reset()
        return pending

    # -- the /check entry (handler threads) ----------------------------------

    def handle_profile(self, body: bytes) -> Tuple[int, dict]:
        """``POST /profile``: one bounded on-demand device-profiling
        window (obs.profiling) on the serving process — jax.profiler
        trace + per-device memory high-water — without stopping the
        daemon.  Runs on the handler thread: capture is passive (no
        device dispatch of its own), so in-flight checking traffic IS
        the workload being profiled."""
        try:
            req = protocol.decode_body(body) if body else {}
        except Exception as e:  # noqa: BLE001 — malformed client input
            return 400, {"error": f"bad request: {e!r}"}
        if not isinstance(req, dict):
            return 400, {"error": "bad request: body must be an object"}
        try:
            seconds = float(req.get("seconds", 1.0))
        except (TypeError, ValueError):
            return 400, {"error": "bad request: seconds must be a number"}
        label = str(req.get("label") or "")
        out_dir = req.get("dir")
        if not out_dir:
            stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            name = f"{stamp}-{label}" if label else stamp
            out_dir = os.path.join(self.profile_dir, name)
        try:
            manifest = obs_profiling.capture(out_dir, seconds=seconds,
                                             label=label)
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            return 500, {"error": f"profile capture failed: {e!r}"}
        return 200, {"ok": True, "dir": out_dir, "manifest": manifest}

    def handle_check(self, body: bytes) -> Tuple[int, dict]:
        if self._fatal is not None:
            return 500, {"error": f"device thread failed: {self._fatal}"}
        try:
            payload = protocol.decode_body(body)
            model = protocol.model_from_wire(payload["model"])
            histories = protocol.histories_from_wire(payload["histories"])
            opts = payload.get("opts") or {}
        except Exception as e:  # noqa: BLE001 — malformed client input
            return 400, {"error": f"bad request: {e!r}"}
        ctx = propagate.parse_ctx(payload.get("trace_ctx"))
        attrs = {"histories": len(histories)}
        if ctx:
            # the daemon half of the cross-seam trace: tagged so a
            # later GET /trace?ctx= can slice this request's spans out
            # and obs.export can stitch a flow event to the client span
            attrs[propagate.ATTR_TRACE_ID] = ctx["trace_id"]
            attrs[propagate.ATTR_ROLE] = "daemon"
            attrs["parent_sid"] = ctx["parent_sid"]
        with obs.span("serve/check", cat="serve", **attrs):
            return self._check_flow(payload, model, histories, opts,
                                    ctx["trace_id"] if ctx else None)

    def _check_opts(self, wire_model: dict, opts: dict):
        """Resolve a request's planning/execution option dicts and its
        compatible-group key (shared between /check and /feed so a
        feed delta coalesces with check traffic under the same model
        and options)."""
        from ..ops import wgl

        plan_opts = {
            "slot_cap": opts.get("slot_cap", wgl.DEFAULT_SLOT_CAP),
            "frontier": opts.get("frontier", wgl.DEFAULT_FRONTIER),
            "max_closure": opts.get("max_closure"),
            "max_dispatch": opts.get(
                "max_dispatch", wgl.DEFAULT_MAX_DISPATCH),
        }
        esc = opts.get("escalation")
        exec_opts = {
            "escalation": (
                wgl.ESCALATION_FACTORS if esc is None else tuple(esc)
            ),
            "sufficient_rung": bool(opts.get("sufficient_rung", True)),
            "max_dispatch": plan_opts["max_dispatch"],
        }
        # compatible-group key: requests coalesce into shared dispatch
        # chunks only when the model AND every planning/execution
        # option agree (the wire model dict is canonical-enough: same
        # construction → same dict)
        group_key = (
            json.dumps(wire_model, sort_keys=True, default=repr),
            json.dumps(plan_opts, sort_keys=True),
            json.dumps(
                {**exec_opts, "escalation": list(exec_opts["escalation"])},
                sort_keys=True,
            ),
        )
        return plan_opts, exec_opts, group_key

    def _check_flow(self, payload, model, histories, opts,
                    trace_id: Optional[str]) -> Tuple[int, dict]:
        #: the client's idempotency key (serve.protocol request ids) —
        #: doubles as the verdict-WAL run id, so a retry after a
        #: daemon crash finds its settled partitions under the same id
        req_id = payload.get("req")
        cached = self._dedup_hit(req_id)
        if cached is not None:
            return cached
        if not self.precheck_admit(len(histories)):
            # overload sheds BEFORE the planning half: no encode, no
            # oracle-pool submissions for a request we will refuse
            with self._wake:
                depth = len(self._queue)
                self.stats["rejected"] += 1
            obs.count("jepsen_serve_rejected_total")
            return 503, {
                "error": "backlogged",
                "queue_depth": depth,
                "stopping": self._stopping.is_set(),
            }
        plan_opts, exec_opts, group_key = self._check_opts(
            payload["model"], opts)
        # the decomposition front-end runs handler-side (pure host
        # work): partitionable histories split into per-partition
        # sub-histories whose buckets then coalesce across runs like
        # any others (see _process_group's per-tag merge)
        run = decompose.DecomposedRun(
            model, histories,
            oracle_fallback=bool(opts.get("oracle_fallback", True)),
        )
        # crash-safe resumption: every NEW verdict appends to the WAL
        # under this request's id, and a RETRIED id replays the
        # verdicts a previous daemon life already settled — replay
        # runs BEFORE encode, so settled rows never re-encode and only
        # unsettled partitions re-dispatch (the planner's settled-row
        # skip)
        replayed = 0
        if self._wal is not None:
            run.attach_wal(
                self._wal.sink_for(req_id or protocol.request_id()))
            prior = self._wal_replay.get(req_id) if req_id else None
            if prior:
                replayed = run.replay(prior)
                if replayed:
                    with self._wake:
                        self.stats["replayed"] += replayed
                    obs.count("jepsen_serve_wal_replayed_total", replayed)
        streams = []
        with obs.span("serve/plan", cat="serve", histories=len(histories)):
            for tag, sctx in run.streams():
                planner = planning.Planner(
                    sctx.model, spec=sctx.spec, **plan_opts
                )
                buckets, order = planner.encode_buckets(sctx)
                streams.append(
                    _Stream(tag, sctx.model, sctx.spec, buckets, order)
                )
        req = _Request(run, streams, group_key, model, plan_opts,
                       exec_opts, len(histories), trace_id=trace_id)
        req.replayed = replayed
        if not self.admit(req):
            # planning already submitted this run's unencodable rows
            # to the oracle pool; cancel what has not started — the
            # 503'd client re-runs everything in-process anyway
            req.abandoned = True
            run.abandon_oracles()
            with self._wake:
                depth = len(self._queue)
            return 503, {
                "error": "backlogged",
                "queue_depth": depth,
                "stopping": self._stopping.is_set(),
            }
        if not req.device_done.wait(
            _env_float("JEPSEN_TPU_SERVE_REQUEST_TIMEOUT",
                       DEFAULT_REQUEST_TIMEOUT_S)
        ):
            # nobody will read this request's results.  Only the flag
            # is set here: the DEVICE thread owns ctx once the request
            # is queued (it may be settling rows right now), so it —
            # not this handler — cancels the orphaned oracle futures
            # when it sees the flag (skip path and post-group check);
            # a handler-side abandon would race route_oracle's dict
            # inserts mid-settle
            req.abandoned = True
            return 500, {"error": "device thread timed out"}
        if req.error is not None:
            return 500, {"error": req.error}
        run.drain_oracles()
        diag = dict(req.diag)
        # the resumption evidence the chaos harness keys on: how many
        # slots came pre-settled from the WAL vs settled in total
        diag["replayed"] = req.replayed
        diag["settled"] = run.settled_count()
        body = {
            "results": protocol.sanitize_results(run.results()),
            "diag": diag,
        }
        self._dedup_store(req_id, 200, body)
        return 200, body

    # -- the /feed entry (handler threads) -----------------------------------

    def handle_feed(self, body: bytes) -> Tuple[int, dict]:
        """Streaming ingest (doc/checker-service.md "Online
        checking"): one endpoint, three ops — ``open`` a session,
        ``append`` deltas (whole histories and/or completed-op dicts),
        ``close`` for the authoritative merged results.  Every delta
        encodes, buckets, and dispatches THROUGH THE DEVICE THREAD the
        moment it arrives, so a violation at op 40k settles (and hits
        the WAL, and every ``/watch`` subscriber) near op 40k instead
        of at run end."""
        if self._fatal is not None:
            return 500, {"error": f"device thread failed: {self._fatal}"}
        try:
            payload = protocol.decode_body(body)
            fop = payload.get("op")
        except Exception as e:  # noqa: BLE001 — malformed client input
            return 400, {"error": f"bad request: {e!r}"}
        with obs.span("serve/feed", cat="serve", op=str(fop)):
            if fop == "open":
                return self._feed_open(payload)
            if fop == "append":
                return self._feed_append(payload)
            if fop == "close":
                return self._feed_close(payload)
            return 400, {"error": f"unknown feed op {fop!r}"}

    def _feed_open(self, payload) -> Tuple[int, dict]:
        try:
            model = protocol.model_from_wire(payload["model"])
            opts = payload.get("opts") or {}
            plan_opts, exec_opts, group_key = self._check_opts(
                payload["model"], opts)
        except Exception as e:  # noqa: BLE001 — malformed client input
            return 400, {"error": f"bad request: {e!r}"}
        ctx = propagate.parse_ctx(payload.get("trace_ctx"))
        #: the session id doubles as the verdict-WAL run id, so a
        #: session re-opened after a daemon crash (same client req id)
        #: replays its settled partitions into resumed deltas
        sid = payload.get("req") or protocol.request_id()
        run = decompose.DecomposedRun(
            model, [],
            oracle_fallback=bool(opts.get("oracle_fallback", True)),
            lazy=True,
        )
        prior: dict = {}
        if self._wal is not None:
            run.attach_wal(self._wal.sink_for(sid))
            prior = self._wal_replay.get(sid) or {}
        s = _FeedSession(sid, run, model, plan_opts, exec_opts,
                         group_key, ctx["trace_id"] if ctx else None,
                         dict(prior))
        with self._wake:
            if self._stopping.is_set():
                return 503, {"error": "stopping", "stopping": True}
            if sid in self._feeds:
                # idempotent re-open (retry whose response was lost):
                # the existing session keeps its state
                return 200, {"session": sid, "resumed": True}
            self._feeds[sid] = s
            self.stats["feed_sessions"] += 1
            n_open = len(self._feeds)
        obs.count("jepsen_feed_sessions_total")
        obs.gauge_set("jepsen_feed_open_sessions", n_open)
        return 200, {"session": sid, "resumed": False}

    def _feed_session(self, payload):
        sid = payload.get("session")
        with self._wake:
            s = self._feeds.get(sid)
        if s is None:
            return None, (404, {"error": f"unknown feed session {sid!r}"})
        return s, None

    def _feed_append(self, payload) -> Tuple[int, dict]:
        s, err = self._feed_session(payload)
        if s is None:
            return err
        try:
            seq = int(payload.get("seq"))
        except (TypeError, ValueError):
            return 400, {"error": "bad seq"}
        with s.lock:
            if seq <= s.last_seq:
                # retried delta (response lost on the wire): already
                # ingested — ack without re-dispatching anything
                return 200, {"session": s.sid, "seq": seq,
                             "duplicate": True, "accepted": 0,
                             "settled": s.run.settled_count()}
            try:
                histories = protocol.histories_from_wire(
                    payload.get("histories") or [])
            except Exception as e:  # noqa: BLE001 — malformed input
                return 400, {"error": f"bad request: {e!r}"}
            n_client = len(histories)
            ops = payload.get("ops") or []
            all_ops = s.ops
            if ops:
                # op-mode: accumulate the shipped events (real-time
                # order) and probe the assembled prefix history — the
                # P-compositionality bet: grown partitions recheck
                # cheaply in isolation, so the probe prices like its
                # changed keys, not like the whole run.  The buffer
                # commits only on dispatch success: a 503'd delta the
                # client retries must not double-ingest its ops.
                from ..history import History

                try:
                    all_ops = s.ops + [dict(o) for o in ops]
                    probe = History.from_dicts(all_ops)
                except Exception as e:  # noqa: BLE001 — malformed input
                    return 400, {"error": f"bad ops: {e!r}"}
                histories = list(histories) + [probe]
            base = s.run.n
            code, resp = self._feed_dispatch(s, histories,
                                             payload.get("t_inv"))
            if code != 200:
                return code, resp
            s.history_idx.extend(range(base, base + n_client))
            if ops:
                s.ops = all_ops
                s.probe_idx = base + n_client
                s.probed_n = len(s.ops)
            s.last_seq = seq
            resp["seq"] = seq
            return code, resp

    def _feed_dispatch(self, s: _FeedSession, histories,
                       t_inv) -> Tuple[int, dict]:
        """Ingest one delta: extend the session run, replay any WAL
        rows a previous daemon life settled for the fresh slots,
        encode ONLY the new rows, and push them through the device
        thread like any admitted request (coalescing with concurrent
        traffic under the session's group key)."""
        if not histories:
            return 200, {"session": s.sid, "accepted": 0, "rows": 0,
                         "replayed": 0,
                         "settled": s.run.settled_count()}
        if not self.precheck_admit(len(histories)):
            with self._wake:
                depth = len(self._queue)
                self.stats["rejected"] += 1
            obs.count("jepsen_serve_rejected_total")
            return 503, {
                "error": "backlogged",
                "queue_depth": depth,
                "stopping": self._stopping.is_set(),
            }
        rows = s.run.extend(histories)
        replayed = 0
        if s.prior:
            replayed = s.run.replay(s.prior)
            if replayed:
                with self._wake:
                    self.stats["replayed"] += replayed
                obs.count("jepsen_serve_wal_replayed_total", replayed)
        streams = []
        with obs.span("serve/feed-plan", cat="serve",
                      histories=len(histories)):
            for tag, sctx in s.run.streams():
                idxs = [i for c, i in rows if c is sctx]
                if not idxs:
                    continue
                planner = planning.Planner(
                    sctx.model, spec=sctx.spec, **s.plan_opts,
                )
                buckets, order = planner.encode_rows(sctx, idxs)
                streams.append(
                    _Stream(tag, sctx.model, sctx.spec, buckets, order))
        req = _FeedDelta(s.run, streams, s.group_key, s.model,
                         s.plan_opts, s.exec_opts, len(histories),
                         trace_id=s.trace_id)
        # the row budget must see THIS delta's queued footprint, not
        # the whole session run _Request.rows would count
        req.rows = len(rows)
        if not self.admit(req):
            req.abandoned = True
            s.run.abandon_oracles()
            with self._wake:
                depth = len(self._queue)
            return 503, {
                "error": "backlogged",
                "queue_depth": depth,
                "stopping": self._stopping.is_set(),
            }
        if not req.device_done.wait(
            _env_float("JEPSEN_TPU_SERVE_REQUEST_TIMEOUT",
                       DEFAULT_REQUEST_TIMEOUT_S)
        ):
            req.abandoned = True
            return 500, {"error": "device thread timed out"}
        if req.error is not None:
            return 500, {"error": req.error}
        s.run.drain_oracles()
        if t_inv is not None:
            try:
                # detect-time minus invoke-time: the monitor's core
                # promise, as a histogram
                obs.observe("jepsen_feed_ingest_lag_seconds",
                            max(0.0, time.time() - float(t_inv)))
            except (TypeError, ValueError):
                pass
        with self._wake:
            self.stats["feed_deltas"] += 1
            self.stats["feed_histories"] += len(histories)
        obs.count("jepsen_feed_deltas_total")
        obs.count("jepsen_feed_histories_total", len(histories))
        return 200, {"session": s.sid, "accepted": len(histories),
                     "rows": len(rows), "replayed": replayed,
                     "settled": s.run.settled_count(),
                     "diag": dict(req.diag)}

    def _feed_close(self, payload) -> Tuple[int, dict]:
        req_id = payload.get("req")
        cached = self._dedup_hit(req_id)
        if cached is not None:
            return cached
        s, err = self._feed_session(payload)
        if s is None:
            return err
        with s.lock:
            final_idx = s.probe_idx
            if s.ops and s.probed_n < len(s.ops):
                # ops arrived since the last probe: run the
                # authoritative final check over the complete history
                from ..history import History

                try:
                    final = History.from_dicts(s.ops)
                except Exception as e:  # noqa: BLE001 — malformed input
                    return 400, {"error": f"bad ops: {e!r}"}
                final_idx = s.run.n
                code, resp = self._feed_dispatch(s, [final], None)
                if code != 200:
                    return code, resp
            s.run.drain_oracles()
            results = s.run.results()
            out = [results[i] for i in s.history_idx]
            if final_idx is not None:
                out.append(results[final_idx])
            body = {
                "results": protocol.sanitize_results(out),
                "diag": {
                    "session": s.sid,
                    "deltas": s.last_seq + 1,
                    "histories": len(s.history_idx),
                    "ops": len(s.ops),
                    "settled": s.run.settled_count(),
                    "partitions": s.run.n_partitions,
                },
            }
        with self._wake:
            self._feeds.pop(s.sid, None)
            n_open = len(self._feeds)
        obs.gauge_set("jepsen_feed_open_sessions", n_open)
        self._dedup_store(req_id, 200, body)
        return 200, body

    # -- the /watch channel (handler threads) --------------------------------

    def _watch_enter(self) -> None:
        with self._wake:
            self._watchers += 1
            n = self._watchers
        obs.gauge_set("jepsen_watch_subscribers", n)

    def _watch_exit(self) -> None:
        with self._wake:
            self._watchers -= 1
            n = self._watchers
        obs.gauge_set("jepsen_watch_subscribers", n)

    # -- the /elle entry (handler threads) -----------------------------------

    def handle_elle(self, body: bytes) -> Tuple[int, dict]:
        """Screen a batch of encoded dependency graphs (the Elle
        transactional screens) on the resident executor; concurrent
        /elle batches coalesce same-(bucket, profile) graphs into
        shared dispatches (see :meth:`_process_elle`)."""
        if self._fatal is not None:
            return 500, {"error": f"device thread failed: {self._fatal}"}
        try:
            payload = protocol.decode_body(body)
            graphs = protocol.elle_graphs_from_wire(payload["graphs"])
        except Exception as e:  # noqa: BLE001 — malformed client input
            return 400, {"error": f"bad request: {e!r}"}
        ctx = propagate.parse_ctx(payload.get("trace_ctx"))
        attrs = {"graphs": len(graphs)}
        if ctx:
            attrs[propagate.ATTR_TRACE_ID] = ctx["trace_id"]
            attrs[propagate.ATTR_ROLE] = "daemon"
            attrs["parent_sid"] = ctx["parent_sid"]
        with obs.span("serve/elle", cat="serve", **attrs):
            return self._elle_flow(graphs,
                                   ctx["trace_id"] if ctx else None,
                                   req_id=payload.get("req"))

    def _elle_flow(self, graphs, trace_id: Optional[str],
                   req_id: Optional[str] = None) -> Tuple[int, dict]:
        cached = self._dedup_hit(req_id)
        if cached is not None:
            return cached
        req = _ElleRequest(graphs, trace_id=trace_id)
        if not self.admit(req):
            with self._wake:
                depth = len(self._queue)
            return 503, {
                "error": "backlogged",
                "queue_depth": depth,
                "stopping": self._stopping.is_set(),
            }
        if not req.device_done.wait(
            _env_float("JEPSEN_TPU_SERVE_REQUEST_TIMEOUT",
                       DEFAULT_REQUEST_TIMEOUT_S)
        ):
            req.abandoned = True
            return 500, {"error": "device thread timed out"}
        if req.error is not None:
            return 500, {"error": req.error}
        body = {
            "results": protocol.elle_results_to_wire(req.results or []),
            "diag": req.diag,
        }
        self._dedup_store(req_id, 200, body)
        return 200, body


def _make_handler(daemon: CheckerDaemon):
    class Handler(BaseHTTPRequestHandler):
        # one daemon per handler class: bound at server build time
        protocol_version = "HTTP/1.1"

        def _reply(self, code: int, body: bytes,
                   ctype: str = "application/json"):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _reply_json(self, code: int, payload: dict):
            self._reply(code, protocol.encode_body(payload))

        def do_GET(self):  # noqa: N802 — http.server API, jt: thread-entry
            try:
                if self.path == "/healthz":
                    ok = daemon._fatal is None
                    self._reply_json(200 if ok else 500, {
                        "ok": ok,
                        "error": daemon._fatal,
                        "platform": daemon._platform,
                        "uptime_s": round(time.time() - daemon.t_start, 1),
                    })
                elif self.path == "/status":
                    self._reply_json(200, daemon.status())
                elif self.path == "/metrics":
                    # live scrape — the SAME formatter as the at-exit
                    # metrics.prom dump (obs.render_prom)
                    self._reply(200, obs.render_prom().encode(),
                                "text/plain; version=0.0.4")
                elif self.path.startswith("/trace"):
                    from urllib.parse import parse_qs, urlparse

                    q = parse_qs(urlparse(self.path).query)
                    ctx = (q.get("ctx") or [""])[0]
                    if not ctx:
                        self._reply_json(400, {"error": "missing ctx"})
                    else:
                        self._reply_json(200, daemon.trace_dump(ctx))
                elif self.path.startswith("/watch"):
                    self._serve_watch()
                else:
                    self._reply_json(404, {"error": "not found"})
            except BrokenPipeError:
                pass

        def _serve_watch(self):
            """The verdict watch channel: settled verdicts as
            server-sent events tailing the verdict WAL.  Each event's
            ``id:`` is the WAL's logical valid-row offset (damaged
            lines consume no offset), so a reconnecting subscriber
            sends ``Last-Event-ID`` and resumes exactly after the last
            row it saw — nothing replays twice.  The stream is
            unframed, so the response closes the connection when it
            ends (``Connection: close`` under this handler's
            HTTP/1.1)."""
            if daemon._wal is None:
                self._reply_json(404, {"error": "no verdict WAL"})
                return
            try:
                start = int(self.headers.get("Last-Event-ID")) + 1
            except (TypeError, ValueError):
                start = 0
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-store")
            self.send_header("Connection", "close")
            self.end_headers()
            daemon._watch_enter()
            tail = obs_journal.WalTail(daemon._wal.path, start=start)
            first = True
            quiet_s = 0.0
            try:
                while not daemon._stopping.is_set():
                    events = tail.poll()
                    if events:
                        if first:
                            # the catch-up burst: rows that settled
                            # before this subscriber connected/resumed
                            obs.count("jepsen_watch_replay_rows_total",
                                      len(events))
                        chunk = "".join(
                            f"id: {off}\ndata: "
                            f"{json.dumps(row, sort_keys=True)}\n\n"
                            for off, row in events
                        )
                        self.wfile.write(chunk.encode())
                        self.wfile.flush()
                        obs.count("jepsen_watch_events_total",
                                  len(events))
                        with daemon._wake:
                            daemon.stats["watch_events"] += len(events)
                        quiet_s = 0.0
                    else:
                        time.sleep(0.1)
                        quiet_s += 0.1
                        if quiet_s >= 5.0:
                            # a dead subscriber only surfaces on write:
                            # ping through quiet stretches so stale
                            # watcher threads reap promptly
                            self.wfile.write(b": keep-alive\n\n")
                            self.wfile.flush()
                            quiet_s = 0.0
                    first = False
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass  # subscriber went away — normal lifecycle
            finally:
                daemon._watch_exit()

        def do_POST(self):  # noqa: N802 — http.server API, jt: thread-entry
            try:
                n = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(n) if n else b""
                if self.path == "/check":
                    code, payload = daemon.handle_check(body)
                    self._reply_json(code, payload)
                elif self.path == "/elle":
                    code, payload = daemon.handle_elle(body)
                    self._reply_json(code, payload)
                elif self.path == "/feed":
                    code, payload = daemon.handle_feed(body)
                    self._reply_json(code, payload)
                elif self.path == "/profile":
                    code, payload = daemon.handle_profile(body)
                    self._reply_json(code, payload)
                elif self.path == "/shutdown":
                    self._reply_json(200, daemon.request_shutdown())
                else:
                    self._reply_json(404, {"error": "not found"})
            except BrokenPipeError:
                pass

        def log_message(self, fmt, *args):
            pass  # the daemon's obs metrics are the log of record

    return Handler


def serve(host: str = protocol.DEFAULT_HOST,
          port: Optional[int] = None,
          *,
          window: Optional[int] = None,
          block: bool = True,
          **kw) -> CheckerDaemon:
    """Build and start a checker daemon (the ``cli serve --checker``
    / ``python -m jepsen_tpu.serve`` entry)."""
    if port is None:
        port = int(os.environ.get("JEPSEN_TPU_SERVE_PORT",
                                  protocol.DEFAULT_PORT))
    if "journal_path" not in kw:
        # the production entry journals by default (the constructor
        # default stays off for in-process/test daemons): path from
        # JEPSEN_TPU_JOURNAL, falsy values disable
        jp = os.environ.get("JEPSEN_TPU_JOURNAL",
                            obs_journal.DEFAULT_FILENAME)
        if jp.lower() in ("0", "false", "off", "no", ""):
            jp = None
        kw["journal_path"] = jp
    if "wal_path" not in kw:
        # crash-safe by default at the production entry, like the
        # dispatch journal: verdicts append to JEPSEN_TPU_WAL and a
        # restarted daemon replays them into retried request ids
        # (doc/checker-service.md "Failure modes & recovery")
        wp = os.environ.get("JEPSEN_TPU_WAL",
                            obs_journal.DEFAULT_WAL_FILENAME)
        if wp.lower() in ("0", "false", "off", "no", ""):
            wp = None
        kw["wal_path"] = wp
    if "drift" not in kw:
        # drift sentinel on by default at the production entry (it
        # rides the journal, so a disabled journal disables it too);
        # falsy JEPSEN_TPU_DRIFT opts out explicitly
        dr = os.environ.get("JEPSEN_TPU_DRIFT", "1")
        kw["drift"] = dr.lower() not in ("0", "false", "off", "no", "")
    if "aot_cache_dir" not in kw:
        # the fleet tier's shared AOT executable cache
        # (doc/checker-service.md "Fleet tier"): record every cold
        # compile, pre-warm them all at startup.  Off unless the env
        # names a directory; falsy values disable.
        ad = os.environ.get("JEPSEN_TPU_SERVE_AOT_CACHE", "")
        if ad.lower() in ("0", "false", "off", "no", ""):
            ad = None
        kw["aot_cache_dir"] = ad
    # a persistent compile cache survives daemon crashes: the
    # supervised restart, and the AOT manifest replay at startup, load
    # compiled kernels from disk instead of paying every cold compile
    # again (JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache)
    from ..platform import configure_compile_cache

    configure_compile_cache()
    d = CheckerDaemon(host, port, window=window, **kw)
    return d.start(block=block)


def supervise(child_args, *, max_restarts: int = 16,
              backoff_s: float = 1.0, max_backoff_s: float = 30.0,
              env: Optional[dict] = None,
              _state: Optional[dict] = None,
              _signals: bool = True) -> int:
    """``serve --supervise``: run the daemon as a child process and
    restart it whenever it dies abnormally (kill -9, device wedge, OOM
    — the faults the self-chaos harness injects).  The restarted child
    inherits this process's environment (or ``env`` when given — the
    fleet supervisor's per-member WAL/journal overrides), so it
    re-warms from the same dispatch journal, verdict WAL, and
    jit/AOT cache paths: clients that retry their request ids replay
    settled verdicts instead of recomputing them.  Returns the child's
    final exit code — 0 on a clean exit (/shutdown) or supervisor
    signal, the last crash code once the restart budget is exhausted.

    ``_state``/``_signals`` are :func:`supervise_fleet` seams: the
    fleet runs one ``supervise`` per member on worker threads, where
    ``signal.signal`` is illegal — it installs ONE handler on the main
    thread and terminates every member through its shared state box."""
    import signal
    import subprocess
    import sys

    cmd = [sys.executable, "-m", "jepsen_tpu.serve", *child_args]
    state = _state if _state is not None else {"sig": None, "proc": None}

    def _forward(signum, frame):  # jt: thread-entry
        state["sig"] = signum
        p = state["proc"]
        if p is not None and p.poll() is None:
            p.terminate()

    if _signals:
        for s in (signal.SIGTERM, signal.SIGINT):
            signal.signal(s, _forward)
    restarts = 0
    delay = backoff_s
    while True:
        proc = subprocess.Popen(cmd, env=env)
        state["proc"] = proc
        rc = proc.wait()  # jt: allow[net-timeout] — the supervisor's whole job is blocking on the child's lifetime
        if state["sig"] is not None:
            return 0
        if rc == 0:
            return 0
        restarts += 1
        if restarts > max_restarts:
            print(f"jepsen-tpu serve: restart budget exhausted "
                  f"(rc={rc})", file=sys.stderr)
            return rc
        print(f"jepsen-tpu serve: child exited rc={rc}; restart "
              f"{restarts}/{max_restarts} in {delay:.1f}s",
              file=sys.stderr)
        time.sleep(delay)
        delay = min(delay * 2, max_backoff_s)


def fleet_member_env(i: int, base_env: Optional[dict] = None) -> dict:
    """One fleet member's environment: chip ``i`` of the host and no
    other (libtpu's per-process visibility, :func:`platform.chip_env`
    — a chip belongs to one process, so members must not race for
    one), and the dispatch journal and verdict WAL with a ``-<i>``
    suffix (two daemons appending to one WAL would interleave torn
    rows).  The AOT cache dir is left UNTOUCHED — sharing compiled
    executables across members is the fleet cache's whole point (the
    manifest is multi-writer-safe)."""
    from ..platform import chip_env

    env = dict(os.environ if base_env is None else base_env)
    env.update(chip_env(i))
    for var, default in (
        ("JEPSEN_TPU_JOURNAL", obs_journal.DEFAULT_FILENAME),
        ("JEPSEN_TPU_WAL", obs_journal.DEFAULT_WAL_FILENAME),
    ):
        cur = env.get(var, default)
        if cur.lower() in ("0", "false", "off", "no", ""):
            continue
        root, ext = os.path.splitext(cur)
        env[var] = f"{root}-{i}{ext}"
    return env


def supervise_fleet(n: int, child_args, *,
                    base_port: Optional[int] = None,
                    max_restarts: int = 16, backoff_s: float = 1.0,
                    max_backoff_s: float = 30.0) -> int:
    """``serve --supervise --fleet N``: N supervised daemons on one
    host — ports ``base_port..base_port+N-1``, member *i* on chip *i*,
    per-member WAL/journal paths (:func:`fleet_member_env`), one shared
    AOT executable cache.  More members than the host has chips is a
    ``ValueError`` (unless ``JAX_PLATFORMS=cpu`` asks for a CPU fleet).
    The admission/device split (:class:`AdmissionState`) is what makes
    this just config: each member owns its own queue + executor pair,
    and the router (serve.router) spreads keys across them.  Returns
    the worst member exit code (0 when every member exited clean)."""
    import signal
    import sys

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        from ..platform import local_tpu_chips

        chips = local_tpu_chips()
        if n > chips:
            raise ValueError(
                f"--fleet {n} needs one TPU chip per member, and this "
                f"host has {chips}; run at most {chips} members, or set "
                "JAX_PLATFORMS=cpu for a CPU fleet")
    if base_port is None:
        base_port = int(os.environ.get("JEPSEN_TPU_SERVE_PORT",
                                       protocol.DEFAULT_PORT))
    # each member gets its own --port; strip any caller-supplied one
    args = []
    skip = False
    for a in child_args:
        if skip:
            skip = False
            continue
        if a == "--port":
            skip = True
            continue
        args.append(a)
    boxes = [{"sig": None, "proc": None} for _ in range(n)]

    def _forward(signum, frame):  # jt: thread-entry
        for b in boxes:
            b["sig"] = signum
            p = b["proc"]
            if p is not None and p.poll() is None:
                p.terminate()

    # ONE handler on the main thread (signal.signal is main-thread
    # only); member supervisors run with _signals=False underneath it
    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _forward)
    rcs = [0] * n

    def _member(i: int) -> None:  # jt: thread-entry
        rcs[i] = supervise(
            [*args, "--port", str(base_port + i)],
            max_restarts=max_restarts, backoff_s=backoff_s,
            max_backoff_s=max_backoff_s, env=fleet_member_env(i),
            _state=boxes[i], _signals=False,
        )

    threads = [
        threading.Thread(target=_member, args=(i,),
                         name=f"jepsen-fleet-{i}", daemon=True)
        for i in range(n)
    ]
    for t in threads:
        t.start()
    members = ", ".join(str(base_port + i) for i in range(n))
    print(f"jepsen-tpu serve: supervising fleet of {n} "
          f"(ports {members})", file=sys.stderr)
    for t in threads:
        t.join()  # jt: allow[net-timeout] — the fleet supervisor's whole job is blocking on member lifetimes
    return max(rcs)
