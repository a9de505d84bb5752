"""The pure per-run **planning** half of the checker engine.

The pipeline used to be one function owning everything from host
encode to device dispatch.  Serving many concurrent runs from one
resident device (jepsen_tpu.serve) forces the split the ROADMAP names:
everything *per-run and pure* lives here — encoding histories into
per-(E, C) shape buckets, stacking a bucket into padded arrays, and
planning its kernel route (``wgl.plan_bucket``) — while everything
*device-owning and shared* (the dispatch window, chunk dispatch,
escalation reruns, oracle-pool interaction) lives in
:mod:`jepsen_tpu.engine.execution`.

Two compositions consume this module:

- :func:`jepsen_tpu.engine.pipeline.run` — one run, one
  :class:`RunContext`, one private executor: ``Planner.stream`` yields
  planned buckets as encode proceeds (a full bucket flushes while
  later histories are still encoding, preserving the encode/device
  overlap the pipelined engine was built for).
- the checker service daemon (:mod:`jepsen_tpu.serve.daemon`) — many
  concurrent runs share ONE resident executor: request handlers call
  :meth:`Planner.encode_buckets` (pure, parallel-safe), the daemon's
  device thread merges same-key buckets *across runs* and stacks each
  merged bucket once via :meth:`Planner.plan_rows`.

Row identity is an opaque token ``(ctx, idx)``: every planned row
carries the :class:`RunContext` it belongs to, so the execution layer
can interleave rows from many runs in one dispatch and still route
each verdict home (per-client result routing is what makes cross-run
coalescing sound).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import obs

#: rows a shape bucket accumulates before flushing mid-stream.  Kept at
#: the default dispatch cap so ordinary batches flush exactly once per
#: bucket (identical routing/compile behavior to the one-shot encode),
#: while keyspaces past it stream: encode of flush k+1 overlaps the
#: device work of flush k.
DEFAULT_FLUSH_ROWS = 16384

_UNSET = object()


def _note_settle_times(result: dict) -> None:
    """Record the run's time-to-first-verdict / time-to-violation
    gauges (doc/observability.md "Online checking") the moment a slot
    settles: seconds since the tracer's wall origin, written once — the
    FIRST settle and the FIRST ``valid? = false`` verdict win.  This is
    the summary seam the online monitor keys on (a violation at op 40k
    should show a detect time near op 40k, not at run end)."""
    if not obs.enabled():
        return
    import time as _time

    reg = obs.registry()
    dt = _time.time() - obs.tracer().wall_origin
    if reg.value("jepsen_run_first_verdict_seconds") is None:
        obs.gauge_set("jepsen_run_first_verdict_seconds", round(dt, 6))
    if (result.get("valid?") is False
            and reg.value("jepsen_run_first_violation_seconds") is None):
        obs.gauge_set("jepsen_run_first_violation_seconds", round(dt, 6))


def flush_rows_default() -> int:
    """Resolved streaming flush threshold:
    ``JEPSEN_TPU_ENGINE_FLUSH_ROWS`` > active calibration
    (doc/tuning.md) > :data:`DEFAULT_FLUSH_ROWS`."""
    from ..tune import artifact as _cal

    return _cal.resolve_knob(
        "JEPSEN_TPU_ENGINE_FLUSH_ROWS",
        lambda v: max(1, int(v)),
        lambda cal: max(1, cal.flush_rows()),
        DEFAULT_FLUSH_ROWS,
    )


class RunContext:
    """One run's bookkeeping: the histories being checked, their result
    slots, and the oracle hand-off state.

    Owns no device resources — a resident execution layer can
    interleave rows from many live contexts into shared dispatches.
    Thread contract (enforced by phase ordering, not locks): during
    planning only the planning thread touches the context; during
    execution only the executor thread assigns results; the consumer
    may call :meth:`drain_oracles` / read :attr:`results` only after
    execution for this context has finished (the service daemon
    signals that with a per-request event, the in-process pipeline by
    plain sequencing).
    """

    def __init__(
        self,
        model,
        histories: Sequence,
        *,
        spec=_UNSET,
        models: Optional[Sequence] = None,
        oracle_fallback: bool = True,
        oracle_budget_s: Optional[float] = None,
    ):
        from ..ops.step_kernels import spec_for

        self.model = model
        self.histories = histories
        #: per-history model overrides — the decomposition front-end's
        #: sub-history contexts carry one seeded sub-model per row
        #: (same spec family as ``model``, different init state); None
        #: = every history checks against ``model``
        self.models = models
        self.spec = spec_for(model) if spec is _UNSET else spec
        self.oracle_fallback = oracle_fallback
        self.oracle_budget_s = oracle_budget_s
        self.results: List[Optional[dict]] = [None] * len(histories)
        self.oracle_futs: Dict[int, Tuple[Any, str]] = {}
        self.oracle_deferred: List[Tuple[int, str]] = []
        #: optional ``(ctx, idx, result)`` hook fired once per settled
        #: slot — the verdict-WAL seam.  Monotone: a slot that already
        #: holds a verdict never re-settles, so the hook fires at most
        #: once per index.
        self.on_settle: Optional[Any] = None

    def model_for(self, idx: int):
        """The model history ``idx`` checks against (encode init state
        and oracle fallback both read this, so the two can never
        disagree about a sub-history's seeded state)."""
        return self.model if self.models is None else self.models[idx]

    def append(self, history, model=None) -> int:
        """Grow the context by one history (its result slot rides
        along); returns the new index.  This is the streaming-
        decomposition seam: the front-end's stage-0 split feeds
        sub-histories in one at a time, interleaved with encode, so
        the context must accept rows incrementally.  Only valid while
        planning this context is still in progress (the same
        phase-ordering contract as the rest of the class)."""
        idx = len(self.histories)
        self.histories.append(history)
        self.results.append(None)
        if self.models is not None:
            self.models.append(model if model is not None else self.model)
        elif model is not None and model is not self.model:
            self.models = [self.model] * idx + [model]
        return idx

    def assign(self, idx: int, result: dict) -> None:
        """Settle one result slot — monotone accumulation.

        A slot settles exactly once: re-assignment of an
        already-settled index is a no-op, which makes replayed
        (WAL-pre-filled) verdicts authoritative over any re-dispatch
        and lets the settle hook fire at most once per index.
        """
        if self.results[idx] is not None:
            return
        self.results[idx] = result
        _note_settle_times(result)
        if self.on_settle is not None:
            self.on_settle(self, idx, result)

    def settled(self, idx: int) -> bool:
        """True when ``idx`` already holds a verdict (replayed or
        settled this run) — such rows must not re-encode/re-dispatch."""
        return self.results[idx] is not None

    def settled_count(self) -> int:
        return sum(1 for r in self.results if r is not None)

    def route_oracle(self, idx: int, engine_tag: str,
                     unresolved_tag: str) -> None:
        """Queue one history for the CPU oracle worker pool (running
        concurrently with device work), or tag it unknown when the
        caller runs the oracle itself (race mode).

        Budgeted searches (``oracle_budget_s``) are NOT overlapped:
        the budget is a wall-clock deadline, and GIL-sharing worker
        threads would burn it ~workers× faster than the serial path —
        flipping verdicts that passed serially to "unknown".  Those
        defer to a serial drain pass after device work, exactly the
        historical order."""
        from ..checker import linear

        if not self.oracle_fallback:
            self.assign(idx, {"valid?": "unknown",
                              "engine": unresolved_tag})
            return
        if self.oracle_budget_s is not None:
            self.oracle_deferred.append((idx, engine_tag))
            return
        pure = self.spec.pure_fs if self.spec else ()
        self.oracle_futs[idx] = (
            linear.analysis_async(
                self.model_for(idx), self.histories[idx], pure_fs=pure,
                budget_s=self.oracle_budget_s,
            ),
            engine_tag,
        )

    def abandon_oracles(self) -> int:
        """Best-effort cancellation of this run's oracle work — the
        service calls it when a request is refused or timed out AFTER
        planning already submitted searches: queued-not-started
        futures cancel outright (the common case under overload, when
        the pool is the bottleneck); an already-running exponential
        search cannot be interrupted and completes into the discarded
        future (bounded by the pool width).  Returns the number
        cancelled."""
        cancelled = 0
        for fut, _tag in self.oracle_futs.values():
            if fut.cancel():
                cancelled += 1
        self.oracle_futs.clear()
        self.oracle_deferred.clear()
        return cancelled

    def drain_oracles(self) -> None:
        """Collect concurrent oracle verdicts, then run budgeted
        searches serially (see :meth:`route_oracle`)."""
        from ..checker import linear

        for idx, (fut, engine_tag) in self.oracle_futs.items():
            r = fut.result()
            r["engine"] = engine_tag
            self.assign(idx, r)
        pure = self.spec.pure_fs if self.spec else ()
        for idx, engine_tag in self.oracle_deferred:
            if self.settled(idx):
                continue  # replayed verdicts win; skip the search
            r = linear.analysis(
                self.model_for(idx), self.histories[idx], pure_fs=pure,
                budget_s=self.oracle_budget_s,
            )
            r["engine"] = engine_tag
            self.assign(idx, r)


class PlannedBucket:
    """One stacked-and-routed bucket, ready for the execution layer:
    the :class:`~jepsen_tpu.ops.wgl.BucketPlan`, the padded 6-tuple of
    arrays, and one ``(ctx, idx)`` row token per array row."""

    __slots__ = ("key", "plan", "arrays", "rows")

    def __init__(self, key, plan, arrays, rows):
        self.key = key
        self.plan = plan
        self.arrays = arrays
        self.rows = rows


class Planner:
    """Pure per-run planning: stream host encode into per-(E, C) shape
    buckets and plan each flush's kernel route.  Holds no device
    state; safe to run on any thread (the service daemon plans on its
    request-handler threads)."""

    def __init__(
        self,
        model,
        *,
        slot_cap: int,
        frontier: int,
        spec=_UNSET,
        max_closure: Optional[int] = None,
        max_dispatch: Optional[int] = None,
        flush_rows: Optional[int] = None,
        n_devices: int = 1,
    ):
        from ..ops import wgl
        from ..ops.step_kernels import spec_for

        self.model = model
        self.spec = spec_for(model) if spec is _UNSET else spec
        self.slot_cap = slot_cap
        self.frontier = frontier
        self.max_closure = max_closure
        self.max_dispatch = (
            wgl.DEFAULT_MAX_DISPATCH if max_dispatch is None else max_dispatch
        )
        # the flush threshold is a PER-DEVICE feed rate: on an n-device
        # mesh a flush fans its rows out across all n chips, so
        # flushing at the single-chip row count would hand each chip
        # 1/n of a full dispatch — the mesh scales the threshold so
        # every mid-stream flush still saturates the whole slice
        self.flush_rows = max(1, n_devices) * (
            flush_rows_default() if flush_rows is None else max(1, flush_rows)
        )
        #: distinct shape buckets seen (what the bucket-count gauge
        #: reports); flushes can exceed it when a bucket streams
        self.n_buckets = 0
        self.n_flushes = 0

    # -- encoding ---------------------------------------------------------

    def chunk_full(self, rows: int, events: int) -> bool:
        """Whether a pending chunk of ``rows`` histories holding
        ``events`` events is due: :data:`encode.CHUNK_EVENTS
        <jepsen_tpu.ops.encode.CHUNK_EVENTS>` events, or as many
        histories as a flush.  A spec with no columnar encoder gains
        nothing from a chunk: each of its histories is due at once."""
        from ..ops import encode as encode_mod

        return (self.spec is None or self.spec.encode_columns is None
                or events >= encode_mod.CHUNK_EVENTS
                or rows >= self.flush_rows)

    def encode_chunk(self, rows: list) -> list:
        """Encode a chunk of ``(ctx, idx)`` rows at once (the ONE
        shared encode/route step — oracle routing and bucket keying
        cannot diverge between the in-process stream and the service's
        encode_buckets): ``[(token, key, encoded)]`` for the rows that
        encoded, in row order.  An unencodable row routes to the oracle
        as the chunk's encode ends — that search starts NOW, on the
        worker pool, overlapping all remaining encode and device work.  The
        per-history model (``ctx.model_for``) seeds the init state —
        decomposed sub-histories share this planner's spec family but
        each carry their own partition's seeded sub-model.

        A row that already holds a verdict — WAL-replayed before
        encode — is skipped outright: settled rows never re-encode or
        re-dispatch, which is what makes a restarted run re-dispatch
        only its unsettled partitions."""
        from ..ops import encode as encode_mod

        rows = [(ctx, idx) for ctx, idx in rows if not ctx.settled(idx)]
        if not rows:
            return []
        hists = [ctx.histories[idx] for ctx, idx in rows]
        placed = []
        # one span a chunk; its ``events`` are the encoded histories'
        with obs.span("engine/encode", cat="engine") as sp:
            if self.spec is None:
                encs, per_history = [None] * len(rows), []
            else:
                encs, per_history = encode_mod.encode_chunk(
                    hists, [ctx.model_for(idx) for ctx, idx in rows],
                    self.slot_cap, self.spec,
                )
            events = 0
            for tok, h, e in zip(rows, hists, encs):
                if e is not None:
                    placed.append(
                        (tok, encode_mod.bucket_key(e, self.slot_cap), e))
                    events += len(h)
            if sp:
                sp.set("events", events)
                sp.set("histories", len(rows))
        if self.spec is not None:
            for path, k in (("columnar", len(rows) - len(per_history)),
                            ("per_history", len(per_history))):
                if k:
                    obs.count("jepsen_engine_encoded_histories_total", k,
                              path=path)
        for (ctx, idx), e in zip(rows, encs):
            if e is None:
                ctx.route_oracle(idx, "oracle-fallback", "unencodable")
        return placed

    @staticmethod
    def place(item, buckets, order):
        """Append one :meth:`encode_chunk` result to its bucket; returns
        the bucket key."""
        tok, key, e = item
        acc = buckets.get(key)
        if acc is None:
            acc = buckets[key] = ([], [])
            order.append(key)
        acc[0].append(e)
        acc[1].append(tok)
        return key

    def encode_buckets(self, ctx: RunContext):
        """Encode every history of ``ctx`` into raw (unstacked) shape
        buckets: ``(buckets, order)`` with ``buckets[key] = (encs,
        tokens)``.  Unencodable histories route to the oracle as their
        chunk is encoded.  This is the service path: raw buckets from
        many contexts merge by key before a single stack+plan, so
        same-shape requests share compiled executables AND dispatch
        rows."""
        return self.encode_rows(ctx, range(len(ctx.histories)))

    def encode_rows(self, ctx: RunContext, idxs):
        """:meth:`encode_buckets` restricted to the given indices —
        the streaming-ingest delta path (``POST /feed``): a feed
        append encodes ONLY the rows :meth:`DecomposedRun.extend
        <jepsen_tpu.engine.decompose.DecomposedRun.extend>` just
        created, so per-partition sub-histories bucket and dispatch as
        operations complete instead of waiting for session close.
        Settled (WAL-replayed) rows skip as everywhere else."""
        buckets: Dict[Any, Tuple[list, list]] = {}
        order: List[Any] = []
        pending: list = []
        events = 0
        for idx in idxs:
            pending.append((ctx, idx))
            events += len(ctx.histories[idx])
            if self.chunk_full(len(pending), events):
                for item in self.encode_chunk(pending):
                    self.place(item, buckets, order)
                pending, events = [], 0
        for item in self.encode_chunk(pending):
            self.place(item, buckets, order)
        return buckets, order

    # -- planning ---------------------------------------------------------

    def plan_rows(self, key, encs: list, rows: list) -> Optional[PlannedBucket]:
        """Stack one bucket's encoded histories and plan its kernel
        route; ``rows`` are opaque ``(ctx, idx)`` tokens aligned with
        ``encs``.  Returns ``None`` for an empty bucket."""
        from ..ops import encode as encode_mod
        from ..ops import wgl

        if not encs:
            return None
        with obs.span("engine/plan", cat="engine") as sp:
            E, C = key
            batch = encode_mod.stack_encoded(encs, rows, E, C)
            arrays = (
                batch.init_state, batch.ev_slot, batch.cand_slot,
                batch.cand_f, batch.cand_a, batch.cand_b,
            )
            self.n_flushes += 1
            plan = wgl.plan_bucket(
                self.model, self.spec, arrays, frontier=self.frontier,
                max_closure=self.max_closure,
                max_dispatch=self.max_dispatch,
            )
            if sp:
                sp.set("rows", len(encs))
                sp.set("E", E)
                sp.set("C", C)
                sp.set("kernel", plan.kernel)
        return PlannedBucket(key, plan, arrays, batch.row_history)

    # -- the streaming composition (in-process pipeline) ------------------

    def open_stream(self) -> "BucketStream":
        """An incremental feed/finish face over this planner — the
        seam that lets a producer interleave OTHER host work (the
        decomposition front-end's stage-0 split) between histories
        instead of handing :meth:`stream` a fully-materialized list."""
        return BucketStream(self)

    def stream(self, ctx: RunContext):
        """Generator: encode ``ctx``'s histories one at a time and
        yield a :class:`PlannedBucket` whenever a bucket fills
        (mid-stream, so the consumer's device work overlaps the
        remaining encode) or at end-of-input.  Unencodable histories
        route to the oracle pool immediately, before any yield."""
        s = self.open_stream()
        for idx in range(len(ctx.histories)):
            yield from s.feed(ctx, idx)
        yield from s.finish()


class BucketStream:
    """One in-progress streaming pass over a :class:`Planner`:
    :meth:`feed` takes one history at a time and encodes them a chunk
    at a time (:meth:`Planner.encode_chunk`), flushing a bucket
    mid-stream when it fills; :meth:`finish` encodes the last chunk,
    plans the residual buckets and yields them
    **largest estimated cost first** — big buckets keep the dispatch
    window occupied while small ones fill the tail (the per-run half
    of the daemon's largest-cost-first scheduling; verdicts are
    order-independent by the engine contract, so the reorder is purely
    a throughput decision, and ties keep first-seen order so the
    sequence stays deterministic)."""

    __slots__ = ("planner", "buckets", "order", "finished", "pending",
                 "pending_events")

    def __init__(self, planner: Planner):
        self.planner = planner
        self.buckets: Dict[Any, Tuple[list, list]] = {}
        self.order: List[Any] = []  # first-seen bucket order
        self.finished = False
        self.pending: list = []  # rows of the chunk not yet encoded
        self.pending_events = 0

    def feed(self, ctx: RunContext, idx: int):
        """Take history ``idx`` of ``ctx`` into the pending chunk; when
        the chunk is full, encode it and yield a
        :class:`PlannedBucket` for each bucket that fills (so the
        consumer's device work overlaps the remaining encode).  The
        chunk's unencodable histories route to the oracle pool as its
        encode ends, before any yield."""
        if self.finished:
            raise RuntimeError("BucketStream already finished")
        self.pending.append((ctx, idx))
        self.pending_events += len(ctx.histories[idx])
        if self.planner.chunk_full(len(self.pending), self.pending_events):
            yield from self._encode_pending()

    def _encode_pending(self):
        p = self.planner
        rows, self.pending, self.pending_events = self.pending, [], 0
        for item in p.encode_chunk(rows):
            key = p.place(item, self.buckets, self.order)
            # a full bucket flushes into the dispatch window while
            # later histories are still encoding
            acc = self.buckets[key]
            if len(acc[0]) >= p.flush_rows:
                pb = p.plan_rows(key, *acc)
                self.buckets[key] = ([], [])
                if pb is not None:
                    yield pb

    def finish(self):
        """Encode the last chunk (its full buckets yield as in
        :meth:`feed`), plan every residual bucket, then yield
        biggest-cost-first."""
        if self.finished:
            raise RuntimeError("BucketStream already finished")
        yield from self._encode_pending()
        p = self.planner
        planned = []
        for key in self.order:
            pb = p.plan_rows(key, *self.buckets[key])
            if pb is not None:
                planned.append(pb)
        p.n_buckets += len(self.order)
        self.finished = True
        # stable sort: equal-cost buckets keep first-seen order
        planned.sort(key=estimated_cost, reverse=True)
        yield from planned


def estimated_cost(pb: PlannedBucket) -> float:
    """Per-bucket device-cost estimate — the scheduling hook both
    compositions order dispatch by (largest first → better window
    occupancy): the checker service's cross-run coalescer and the
    per-run :meth:`BucketStream.finish` ordering.

    With a calibration artifact active (doc/tuning.md; the measured
    per-shape table ``jepsen_tpu tune`` produces — the
    arXiv:2008.01040 direction, as a direct lookup rather than a
    trained predictor) this returns the interpolated **measured
    seconds** for the bucket's (kernel, E, C, F, rows).  Untuned — or
    for a kernel the table never measured — it falls back to the
    analytic proxy: the dominant footprint term of each kernel family
    (frontier work scales with rows × F·(C+1)·ceil(E/32) state words;
    dense with rows × E, a fixed-width scan).  Oracle-routed buckets
    cost the device nothing either way.  Both forms only RANK buckets;
    absolute scale never changes a verdict."""
    plan = pb.plan
    rows = len(pb.rows)
    if plan.fn is None or plan.disp == 0:
        return 0.0
    from ..tune import artifact as _cal

    cal = _cal.active()
    if cal is not None:
        c = cal.cost(plan.kernel, plan.E, plan.C, plan.frontier, rows)
        if c is not None:
            return c
    if plan.kernel == "dense":
        return float(rows * plan.E)
    if plan.kernel == "cycles":
        # batched boolean closure (the Elle screens): per-row work is
        # the n×n matrix squaring ladder over the packed plane stack,
        # so footprint scales with E² × the plane weight (frontier
        # carries plane_weight(masks, nonadj, closure_impl) on
        # ScreenPlan, 1 on the plain has-cycle CyclePlan).  Under the
        # packed32 closure impl the frontier arrives pre-discounted by
        # W/n ≈ 1/32 — one uint32 word per 32 vertex lanes — so the
        # proxy and the measured cost table rank a word-packed bucket
        # ~32× cheaper than the same profile's uint8 lowering
        return float(rows) * plan.E * plan.E * max(1, plan.frontier)
    words = max(1, -(-plan.E // 32))
    return float(rows * plan.frontier * (plan.C + 1) * words)


def merge_buckets(runs) -> Tuple[Dict[Any, Tuple[list, list]], List[Any]]:
    """Coalesce raw per-run buckets across runs: same-key buckets from
    ``runs`` (an iterable of ``(buckets, order)`` pairs as returned by
    :meth:`Planner.encode_buckets`) concatenate in arrival order into
    one merged ``(encs, tokens)`` per key — the cross-run coalescing
    seam the checker service dispatches through."""
    merged: Dict[Any, Tuple[list, list]] = {}
    merged_order: List[Any] = []
    for buckets, order in runs:
        for key in order:
            encs, tokens = buckets[key]
            acc = merged.get(key)
            if acc is None:
                acc = merged[key] = ([], [])
                merged_order.append(key)
            acc[0].extend(encs)
            acc[1].extend(tokens)
    return merged, merged_order


def finish_run_telemetry(results: Sequence[Optional[dict]]) -> None:
    """Per-subhistory engine-outcome counters (the observable half of
    P-compositional tuning): tpu rows count under their kernel name,
    everything else under its engine tag."""
    from ..ops import wgl

    if not (obs.enabled() and results):
        return
    stats = wgl.batch_stats([r for r in results if r is not None])
    for eng, cnt in stats["engines"].items():
        if eng == "tpu":
            continue
        obs.count("jepsen_engine_rows_total", cnt, engine=eng)
    for k, cnt in stats["kernels"].items():
        obs.count("jepsen_engine_rows_total", cnt, engine=k)
