"""P-compositionality front-end: decompose histories before dispatch.

"Faster linearizability checking via P-compositionality"
(arXiv:1504.00204): when a model is a product of independent
per-partition sub-models — registers per key, locks per name, queue
bags per value — a history is linearizable iff every per-partition
sub-history is, and the product of small searches is exponentially
cheaper than one big one.  The unordered-queue direct checker exploits
this ad hoc; this module is the general pass, running **ahead of**
``wgl.plan_bucket`` in the engine planning layer:

- Models declare the factoring via the partition protocol on
  :mod:`jepsen_tpu.models` (``partition_key(op)`` /
  ``subhistory_model(key)`` / ``partition_op(op, key)``); models
  without a declared partition pass through unchanged.
- :func:`split_history` splits one history into per-partition
  sub-histories at encode time, pairing invocations with completions
  (a dequeue's value lives on the *ok* event) and keeping real-time
  order inside each partition.  Any op whose partition cannot be
  determined keeps the WHOLE history undecomposed — pass-through is
  always sound, so the pass never guesses.
- :class:`DecomposedRun` owns a batch's parent result slots and
  exposes up to two :class:`~jepsen_tpu.engine.planning.RunContext`
  streams — the undecomposed pass-through histories under the parent
  model, and the flattened sub-histories under the partition
  sub-model family — which flow through the UNCHANGED streaming
  bucket path (``Planner.stream`` / ``Planner.encode_buckets``):
  thousands of small sub-histories land in tight same-(E, C) buckets
  instead of one oracle-bound monster, each row tagged ``(ctx, idx)``
  so the execution layer needs no new routing.  Escalation and oracle
  fallback operate per sub-history — one pathological partition no
  longer drags the entire history to the CPU.
- Verdicts AND at settle (:func:`merge_partition_results`): the first
  ``valid? = false`` sub-verdict wins — "first" in deterministic
  partition order, never settle order, so results stay independent of
  window size, bucketing, and interleaving — and the failing
  partition is surfaced as ``failed-partition`` in the result dict.

The pass is on by default (``JEPSEN_TPU_ENGINE_DECOMPOSE=0``
disables; ``check_batch(..., decomposed=False)`` per call) and pinned
verdict-identical to the pass-through path three ways: unit/property
tests (tests/test_decompose.py), ``make decompose-smoke``, and the
op-soup fuzz sweep.  See doc/checker-engines.md "Decomposition
front-end".

Sub-model instances are interned per partition key through a BOUNDED
cache (:data:`DECOMPOSE_CACHE_SIZE`): a wide keyspace must not grow an
unbounded per-key dict the way an uncapped ``lru_cache`` would (the
``ops/cycles.py`` lesson — its closure caches are capped at
``CLOSURE_CACHE_SIZE`` for the same reason).  Evictions are counted as
``jepsen_engine_decompose_cache_evictions_total``.
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import obs
from ..history import FAIL, INVOKE, History
from .planning import RunContext

#: sub-model instances interned per partition key, per run.  Keys come
#: from op values, so a wide keyspace (the millions-of-users traffic
#: shape) could otherwise grow an unbounded per-key map; past the cap
#: the least-recently-used entry evicts (counted below) and the
#: sub-model is simply rebuilt — correctness never depends on a hit.
DECOMPOSE_CACHE_SIZE = 1024

#: partition-fanout histogram buckets: powers of two spanning "barely
#: decomposable" to "wide keyspace" (the seconds-oriented default
#: buckets would squash every fanout into the first bin)
FANOUT_BUCKETS = (2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 1024.0)

#: sentinel key for failed op pairs: dropped from every partition, the
#: same treatment ``linear.prepare`` gives them undecomposed
_DROPPED = object()


def default_enabled() -> bool:
    """Decomposition default: on unless ``JEPSEN_TPU_ENGINE_DECOMPOSE``
    is falsy."""
    return os.environ.get("JEPSEN_TPU_ENGINE_DECOMPOSE", "1").lower() not in (
        "0", "false", "off", "no",
    )


def partitioner(model):
    """The model's ``partition_key`` method, or None when the model
    declares no partition protocol (the base class pins the attribute
    to None)."""
    fn = getattr(model, "partition_key", None)
    return fn if callable(fn) else None


def routing_gain_possible(model) -> bool:
    """Whether splitting ``model``'s histories ahead of dispatch can
    change their routing for the better.  Specs the routing layer
    already hands to a CPU direct algorithm outright
    (``wgl.DIRECT_FIRST_SPECS`` — the unordered queue, whose direct
    checker factors per value *internally*) gain nothing from the
    engine-side split: every sub-history lands back on the same oracle
    path, multiplied by the partition fanout in per-task overhead
    (measured ~12x slower on a 100-value queue corpus).  Those models
    keep their protocol — the oracle's ``_partition_by_key`` and the
    soundness documentation live there — but the engine pass treats
    them as pass-through."""
    from ..ops import step_kernels, wgl

    spec = step_kernels.spec_for(model)
    return spec is None or spec.name not in wgl.DIRECT_FIRST_SPECS


class SubmodelCache:
    """Bounded per-run interning of ``model.subhistory_model(key)``:
    an OrderedDict LRU capped at ``cap`` entries, evictions counted as
    ``jepsen_engine_decompose_cache_evictions_total`` so per-partition
    key explosion is visible in the run's metrics instead of in its
    RSS."""

    __slots__ = ("model", "cap", "_map", "evictions")

    def __init__(self, model, cap: int = DECOMPOSE_CACHE_SIZE):
        self.model = model
        self.cap = max(1, cap)
        self._map: OrderedDict = OrderedDict()
        self.evictions = 0

    def get(self, key):
        try:
            sub = self._map[key]
        except KeyError:
            sub = self.model.subhistory_model(key)
            self._map[key] = sub
            if len(self._map) > self.cap:
                self._map.popitem(last=False)
                self.evictions += 1
                obs.count("jepsen_engine_decompose_cache_evictions_total")
            return sub
        except TypeError:  # unhashable key — protocol impls guard, but
            return self.model.subhistory_model(key)  # never corrupt
        self._map.move_to_end(key)
        return sub


def split_history(model, history, submodel_for=None):
    """Split one history into per-partition sub-histories, or return
    None when it must pass through undecomposed (model declares no
    partition, or any op's partition is undeterminable).

    Returns ``[(key, submodel, subhistory), ...]`` in first-seen key
    order.  Invocations pair with their completions by process (the
    single-outstanding-op discipline ``linear.prepare`` relies on);
    the pair's key resolves from the completion first — a dequeue's
    value, a read's observation live there — then the invocation.
    Failed pairs drop (they never took effect), orphan completions and
    non-client (non-int process) events are skipped exactly as
    ``prepare`` skips them, and each partition keeps its events in
    original real-time order.  Ops enter sub-histories through
    ``model.partition_op`` (identity unless the sub-model speaks a
    different vocabulary); originals are never mutated."""
    key_fn = partitioner(model)
    if key_fn is None:
        return None
    records: List[list] = []  # [invoke_op, completion_op | None]
    rec_of_event: List[int] = []  # per history position, -1 = skipped
    open_of: Dict[int, int] = {}
    for op in history:
        p = op.process
        if not isinstance(p, int):
            rec_of_event.append(-1)
            continue
        if op.type == INVOKE:
            open_of[p] = len(records)
            rec_of_event.append(len(records))
            records.append([op, None])
        else:
            ri = open_of.pop(p, None)
            if ri is None:
                rec_of_event.append(-1)  # orphan completion
                continue
            records[ri][1] = op
            rec_of_event.append(ri)

    keys: List[Any] = []
    for inv, comp in records:
        if comp is not None and comp.type == FAIL:
            keys.append(_DROPPED)  # never took effect; no key needed
            continue
        k = key_fn(comp) if comp is not None else None
        if k is None:
            k = key_fn(inv)
        if k is None:
            return None  # undeterminable partition: pass through whole
        keys.append(k)

    parts: Dict[Any, History] = {}
    order: List[Any] = []
    for pos, op in enumerate(history):
        ri = rec_of_event[pos]
        if ri < 0:
            continue
        k = keys[ri]
        if k is _DROPPED:
            continue
        sub = parts.get(k)
        if sub is None:
            sub = parts[k] = History()
            order.append(k)
        sub.append(model.partition_op(op, k))
    return [
        (
            k,
            submodel_for(k) if submodel_for else model.subhistory_model(k),
            parts[k],
        )
        for k in order
    ]


def merge_partition_results(parts: Sequence[Tuple[Any, dict]]) -> dict:
    """AND a decomposed history's sub-verdicts into one result dict.

    The first ``valid? = false`` sub-verdict wins (then the first
    non-True, i.e. "unknown") — "first" in partition order, which is
    deterministic first-seen order, so the merged result can never
    depend on dispatch interleaving.  The winning sub-result's fields
    (engine, kernel, failed-event — in SUB-history event coordinates)
    carry through, plus ``failed-partition`` naming the partition and
    ``partitions`` with the fanout.  An all-True history reports the
    uniform sub-engine (or ``"mixed"``) so engine-rate stats stay
    honest; whenever ANY sub-history routed to the oracle the count
    rides along as ``oracle-partitions`` — a ``"mixed"`` engine must
    not hide oracle load from routing accounting (decompose-smoke)."""
    n = len(parts)
    n_oracle = sum(
        1 for _k, r in parts
        if str(r.get("engine", "")).startswith("oracle")
    )
    winner = next(
        ((k, r) for k, r in parts if r.get("valid?") is False), None
    )
    if winner is None:
        winner = next(
            ((k, r) for k, r in parts if r.get("valid?") is not True), None
        )
    if winner is not None:
        key, r = winner
        out = dict(r)
        out["failed-partition"] = key
        out["partitions"] = n
        if n_oracle:
            out["oracle-partitions"] = n_oracle
        return out
    engines = {r.get("engine") for _k, r in parts}
    out = {
        "valid?": True,
        "engine": engines.pop() if len(engines) == 1 else "mixed",
        "partitions": n,
    }
    # uniform routing facts carry through (kernel for device rows,
    # algorithm for direct-checker rows) so engine/algorithm stats and
    # assertions see decomposed histories the same way as whole ones;
    # mixed sub-routes omit them rather than guess
    if out["engine"] == "tpu":
        kernels = {r.get("kernel") for _k, r in parts}
        if len(kernels) == 1:
            out["kernel"] = kernels.pop()
    algorithms = {r.get("algorithm") for _k, r in parts}
    if len(algorithms) == 1 and None not in algorithms:
        out["algorithm"] = algorithms.pop()
    if n_oracle:
        out["oracle-partitions"] = n_oracle
    return out


class DecomposedRun:
    """One batch's decomposition bookkeeping: parent result slots plus
    up to two planning streams.

    - ``("main", ctx)`` — pass-through histories under the parent
      model (everything, for models without a partition protocol or
      with decomposition disabled: that degenerate case is bitwise the
      historical single-context run).
    - ``("sub", ctx)`` — the flattened per-partition sub-histories
      under the sub-model family, one
      :class:`~jepsen_tpu.engine.planning.RunContext` whose per-index
      ``models`` carry each partition's seeded sub-model.

    Both streams flow through the unchanged ``Planner`` machinery; the
    in-process pipeline streams them into one executor, the service
    daemon encodes each into raw buckets that coalesce ACROSS runs per
    stream tag.  :meth:`results` assigns pass-through results home and
    ANDs sub-verdicts (:func:`merge_partition_results`) into the
    decomposed parents' slots.
    """

    def __init__(
        self,
        model,
        histories: Sequence,
        *,
        oracle_fallback: bool = True,
        oracle_budget_s: Optional[float] = None,
        enabled: Optional[bool] = None,
        lazy: bool = False,
    ):
        self.model = model
        self._histories = histories
        self.n = len(histories)
        enabled = default_enabled() if enabled is None else bool(enabled)
        self._pass_idx: List[int] = []
        self._parts_of: Dict[int, List[Tuple[Any, int]]] = {}
        self.n_partitions = 0
        self.n_decomposed = 0
        self.cache: Optional[SubmodelCache] = None
        self._active = bool(
            enabled
            and partitioner(model) is not None
            and routing_gain_possible(model)
        )
        if self._active:
            self.cache = SubmodelCache(model)
        self._kw = dict(
            oracle_fallback=oracle_fallback, oracle_budget_s=oracle_budget_s
        )
        self.main_ctx: Optional[RunContext] = None
        self.sub_ctx: Optional[RunContext] = None
        self._fed = False
        self._next_i = 0  # split progress (restartable; see _split)
        #: optional ``(tag, idx, result)`` verdict sink (see
        #: :meth:`attach_wal`); applied to contexts as they are created
        self._settle_sink = None
        if not lazy:
            # eager construction (the service path, and every caller
            # that inspects streams()/counters right away): drain the
            # incremental split here so post-construction state is
            # exactly the historical one
            for _ in self.feed():
                pass

    def feed(self):
        """Generator: classify and split histories ONE AT A TIME,
        yielding ``(ctx, idx)`` for each planner row the moment it
        exists — the streaming seam that lets the pipeline interleave
        the stage-0 split with encode and device dispatch (ROADMAP
        item 3's leftover: the split used to be a serial host preamble
        over the whole batch, so the first dispatch waited on the last
        history's split).  Pass-through rows yield under
        :attr:`main_ctx`, per-partition sub-rows under
        :attr:`sub_ctx`; both contexts grow via
        :meth:`~jepsen_tpu.engine.planning.RunContext.append` as rows
        appear.  On an already-split run (eager construction) it
        replays the existing rows in deterministic order."""
        if self._fed:
            for ctx in (c for c in (self.main_ctx, self.sub_ctx)
                        if c is not None):
                for idx in range(len(ctx.histories)):
                    yield ctx, idx
            if self._next_i < self.n:  # resume an abandoned split
                yield from self._split()
            return
        self._fed = True
        yield from self._split()

    def _split(self):
        """The restartable split loop: :attr:`_next_i` advances the
        moment a history's bookkeeping is complete (before its rows
        yield), so a generator abandoned mid-way — GC closes
        delegated generators — never double-splits or loses a history
        when :meth:`_ensure_fed` restarts the loop."""
        rec = obs.enabled()
        while self._next_i < self.n:
            i = self._next_i
            h = self._histories[i]
            parts = (
                split_history(self.model, h, self.cache.get)
                if self._active else None
            )
            if parts is None or len(parts) <= 1:
                # ≤ 1 partition gains nothing and would only
                # re-tag the result dict; keep it byte-identical
                self._pass_idx.append(i)
                if self.main_ctx is None:
                    self.main_ctx = RunContext(self.model, [], **self._kw)
                    self._bind_sink("main", self.main_ctx)
                idx = self.main_ctx.append(h)
                if rec and self._active:
                    obs.count(
                        "jepsen_engine_decomposed_total",
                        route="passthrough",
                    )
                self._next_i = i + 1
                yield self.main_ctx, idx
                continue
            slots = []
            for key, submodel, subh in parts:
                if self.sub_ctx is None:
                    self.sub_ctx = RunContext(
                        submodel, [], models=[], **self._kw
                    )
                    self._bind_sink("sub", self.sub_ctx)
                slots.append((key, self.sub_ctx.append(subh, submodel)))
            self._parts_of[i] = slots
            self.n_partitions += len(slots)
            self.n_decomposed += 1
            if rec:
                obs.count(
                    "jepsen_engine_decomposed_total", route="decomposed"
                )
                obs.count("jepsen_engine_partitions_total", len(slots))
                obs.registry().histogram(
                    "jepsen_engine_partition_fanout",
                    buckets=FANOUT_BUCKETS,
                ).observe(len(slots))
            self._next_i = i + 1
            for _key, idx in slots:
                yield self.sub_ctx, idx
        if self.main_ctx is None and self.sub_ctx is None:
            # empty batch: keep the historical empty main context so
            # streams()/contexts stay non-surprising
            self.main_ctx = RunContext(self.model, [], **self._kw)
            self._bind_sink("main", self.main_ctx)

    def extend(self, histories: Sequence) -> List[Tuple[RunContext, int]]:
        """Streaming-ingest seam (``POST /feed``): append ``histories``
        to an already-constructed run and drive the restartable split
        over JUST the new tail, returning the fresh ``(ctx, idx)``
        planner rows — prior rows never re-split, re-encode, or
        re-settle, so a feed session dispatches each delta the moment
        it arrives.  Composes with :meth:`replay`: rows a previous
        daemon life already settled (same request id) pre-fill on the
        next replay call and skip encode entirely."""
        self._ensure_fed()  # classify everything before the new tail
        if not isinstance(self._histories, list):
            self._histories = list(self._histories)
        self._histories.extend(histories)
        self.n = len(self._histories)
        return list(self._split())

    def _ensure_fed(self) -> None:
        """Finish the split eagerly for consumers that need the whole
        picture (a lazy run whose feed was never driven — or was
        abandoned mid-way — the restartable :meth:`_split` picks up at
        the first unclassified history)."""
        if not self._fed or self._next_i < self.n:
            self._fed = True
            for _ in self._split():
                pass

    @property
    def contexts(self) -> List[RunContext]:
        self._ensure_fed()
        return [c for c in (self.main_ctx, self.sub_ctx) if c is not None]

    def streams(self) -> List[Tuple[str, RunContext]]:
        """Tagged planning streams — the service daemon merges same-tag
        buckets across concurrent runs (tags are stable per model, so a
        group's requests always align)."""
        self._ensure_fed()
        out: List[Tuple[str, RunContext]] = []
        if self.main_ctx is not None:
            out.append(("main", self.main_ctx))
        if self.sub_ctx is not None:
            out.append(("sub", self.sub_ctx))
        return out

    # -- verdict WAL seam (doc/checker-service.md "Failure modes") --------

    def _bind_sink(self, tag: str, ctx: RunContext) -> None:
        if self._settle_sink is None:
            return
        sink = self._settle_sink

        def _on_settle(_ctx, idx, result, _tag=tag, _sink=sink):
            _sink(_tag, idx, result)

        ctx.on_settle = _on_settle

    def attach_wal(self, sink) -> None:
        """Install a ``(tag, idx, result)`` verdict sink — every slot
        that settles from now on (in already-created contexts AND in
        contexts the split creates later) is appended to the WAL by
        the sink.  ``tag`` is the stream tag (``"main"``/``"sub"``)."""
        self._settle_sink = sink
        if self.main_ctx is not None:
            self._bind_sink("main", self.main_ctx)
        if self.sub_ctx is not None:
            self._bind_sink("sub", self.sub_ctx)

    def replay(self, rows: Dict[Tuple[str, int], dict]) -> int:
        """Pre-fill result slots from replayed WAL rows —
        ``{(tag, idx): result}`` — BYPASSING the settle hook (a
        replayed verdict must not re-append to the WAL).  Settled
        slots never re-encode (the planner skips them), so a restarted
        run re-dispatches only its unsettled partitions.  Returns the
        number of slots filled; out-of-range or already-settled slots
        are ignored (a WAL can outlive the request mix that wrote it).
        """
        self._ensure_fed()
        by_tag = {tag: ctx for tag, ctx in self.streams()}
        n = 0
        for (tag, idx), result in rows.items():
            ctx = by_tag.get(tag)
            if ctx is None or not (0 <= idx < len(ctx.results)):
                continue
            if ctx.results[idx] is None:
                ctx.results[idx] = result
                n += 1
        return n

    def settled_count(self) -> int:
        """Slots holding verdicts across both streams (replay + live)."""
        return sum(c.settled_count() for c in self.contexts)

    def drain_oracles(self) -> None:
        for ctx in self.contexts:
            ctx.drain_oracles()

    def abandon_oracles(self) -> int:
        return sum(ctx.abandon_oracles() for ctx in self.contexts)

    def results(self) -> List[dict]:
        self._ensure_fed()
        out: List[Optional[dict]] = [None] * self.n
        if self.main_ctx is not None:
            for local, parent in enumerate(self._pass_idx):
                out[parent] = self.main_ctx.results[local]
        if self.sub_ctx is not None:
            subres = self.sub_ctx.results
            for parent, slots in self._parts_of.items():
                out[parent] = merge_partition_results(
                    [(key, subres[s]) for key, s in slots]
                )
        return out  # type: ignore[return-value]
