"""The pipelined dispatch engine behind ``wgl.check_batch``.

The serial path ran encode → H2D → kernel → D2H → oracle-fallback
strictly in sequence, so the device idled during host work and the
exponential CPU searches started only after the last dispatch returned.
This module keeps the device saturated the way serving stacks keep
their accelerators fed (continuous batching / input pipelines):

- **Shape buckets.**  Histories encode one at a time and accumulate in
  per-``(E, C)`` buckets (``encode.bucket_key``), so a 30-op history in
  a batch with one 1000-op outlier no longer pays 1000 events of
  padding.  A bucket flushes into device chunks when it reaches
  :data:`DEFAULT_FLUSH_ROWS` rows (huge keyspaces stream: encode of
  the next flush overlaps the previous flush's device work) or at
  end-of-input.
- **Dispatch window.**  Chunk dispatches are asynchronous (JAX returns
  before the kernel finishes); :class:`DispatchWindow` bounds how many
  are in flight and syncs only the oldest when the window fills —
  window=1 degenerates to the historical dispatch-sync-dispatch serial
  path, which is the determinism baseline the tests pin.
- **Concurrent oracle.**  Unencodable histories go to the
  ``checker.linear`` worker pool *immediately* (before any dispatch),
  and oracle-routed/undispatchable buckets join at flush time — so
  oracle wall time hides behind device wall time on mixed batches
  instead of adding to it.
- **P-compositional decomposition.**  Partitionable models (the
  partition protocol on :mod:`jepsen_tpu.models`) split each history
  into per-partition sub-histories BEFORE planning
  (:mod:`jepsen_tpu.engine.decompose`): thousands of small
  sub-histories land in tight same-(E, C) buckets on the dense kernel
  instead of one oracle-bound monster, escalation/oracle fallback run
  per sub-history, and verdicts AND at settle — byte-identical to the
  undecomposed run (``make decompose-smoke`` pins it).
- **Slice-native dispatch.**  With more than one device attached the
  engine resolves a mesh itself
  (:func:`jepsen_tpu.parallel.mesh.engine_default_mesh`) and every
  chunk dispatches through a cached ``shard_map`` wrapper — chunk
  caps scale to ``n × per-chip cap``, rows pad to device multiples
  with neutral rows sliced at settle, and verdicts stay byte-identical
  to the single-device run (``make mesh-smoke`` pins it; see
  doc/checker-engines.md "Slice-native dispatch").

Since the checker-service split, this module is the **composition**
of the engine's two halves, not their implementation:

- :mod:`jepsen_tpu.engine.planning` — the pure per-run layer:
  :class:`~jepsen_tpu.engine.planning.RunContext` (result slots +
  oracle hand-off) and :class:`~jepsen_tpu.engine.planning.Planner`
  (streaming encode → shape buckets → ``wgl.plan_bucket``).
- :mod:`jepsen_tpu.engine.execution` — the device-owning layer:
  :class:`DispatchWindow` and
  :class:`~jepsen_tpu.engine.execution.Executor` (chunk dispatch,
  escalation ladder, footprint-safe chunk caps).

:func:`run` wires one private context/planner/executor per call — the
in-process path.  The checker service daemon (:mod:`jepsen_tpu.serve`)
wires the same two halves differently: one *resident* executor shared
by many concurrent client contexts, with same-shape buckets coalesced
across runs.  Verdicts are a pure function of the histories in both
compositions — never of window size, bucketing, interleaving, or
which composition ran them (``make serve-smoke`` pins the equality).

Kernel routing, escalation rungs, and all result/telemetry contracts
are unchanged from the serial path; the engine assembles the exact
result dicts ``check_batch`` always produced.

Pipeline telemetry (obs registry; doc/observability.md):

- ``jepsen_engine_inflight_depth`` (gauge, high-water): peak
  concurrently in-flight device dispatches — >1 proves overlap.
- ``jepsen_engine_bubble_seconds`` (histogram): host time blocked
  waiting on an in-flight dispatch — the stall the window hides.
- ``jepsen_engine_bucket_count`` (gauge, high-water): peak shape
  buckets one batch split into.

Spans (category ``engine``) nest under ``engine/pipeline`` on its
thread: ``engine/encode`` per chunk of histories, ``engine/plan`` per
flush, ``engine/issue`` per dispatch, ``engine/dispatch`` per wait on
one, ``engine/settle`` per retired dispatch or escalation pass.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from .. import obs
from .execution import (  # noqa: F401 — back-compat re-exports
    DEFAULT_WINDOW,
    DispatchWindow,
    Executor,
    default_window,
)
from .planning import (  # noqa: F401 — back-compat re-exports
    DEFAULT_FLUSH_ROWS,
    Planner,
    RunContext,
    estimated_cost,
    finish_run_telemetry,
)


def run(
    model,
    histories: Sequence,
    *,
    frontier: int,
    slot_cap: int,
    max_closure: Optional[int] = None,
    mesh=None,
    escalation=None,
    oracle_fallback: bool = True,
    sufficient_rung: bool = True,
    max_dispatch: Optional[int] = None,
    oracle_budget_s: Optional[float] = None,
    window: Optional[int] = None,
    decomposed: Optional[bool] = None,
    wal_sink=None,
    wal_replay=None,
) -> List[dict]:
    """Check ``histories`` through the full pipeline; per-history result
    dicts in input order, exactly the shapes ``wgl.check_batch``
    documents.  This is ``check_batch``'s engine — call that, not this,
    unless you are the dispatch layer."""
    from ..parallel import mesh as mesh_mod
    from . import decompose as decompose_mod

    # slice-native by default: no explicit mesh resolves to every
    # attached device whenever more than one is present
    # (doc/checker-engines.md "Slice-native dispatch")
    if mesh is None:
        mesh = mesh_mod.engine_default_mesh()
    n_devices = 1 if mesh is None else int(mesh.devices.size)
    # -- stage 0: the P-compositionality front-end splits partitionable
    # histories into per-partition sub-histories ahead of planning
    # (doc/checker-engines.md "Decomposition front-end"); models
    # without a declared partition — and ``decomposed=False`` /
    # JEPSEN_TPU_ENGINE_DECOMPOSE=0 runs — degenerate to the exact
    # historical single-context run.  lazy=True: the split STREAMS
    # through dec.feed() below, interleaved with encode and device
    # dispatch, instead of running as a serial host preamble over the
    # whole batch (the ROADMAP item 3 follow-up, closed)
    dec = decompose_mod.DecomposedRun(
        model, histories,
        oracle_fallback=oracle_fallback, oracle_budget_s=oracle_budget_s,
        enabled=decomposed, lazy=True,
    )
    # -- crash-safe resumption (doc/checker-service.md "Failure modes
    # & recovery"): WAL-replayed verdicts pre-fill result slots (they
    # never re-encode — the planner skips settled rows), and a settle
    # sink appends every NEW verdict so a later restart resumes here
    if wal_sink is not None:
        dec.attach_wal(wal_sink)
    if wal_replay:
        dec.replay(wal_replay)
    ex = Executor(
        window, mesh=mesh, escalation=escalation,
        sufficient_rung=sufficient_rung, max_dispatch=max_dispatch,
    )

    n_buckets = n_flushes = 0
    with obs.span("engine/pipeline", cat="engine") as sp:
        # -- stage 0+1+2 interleaved: the decomposition front-end's
        # stage-0 split now STREAMS (dec.feed yields each pass-through
        # history / sub-history row the moment it is classified), and
        # each row feeds its stream's planner immediately — so past
        # flush_rows() the split of later histories overlaps the
        # device work of earlier flushes instead of running as a
        # serial host preamble over the whole batch.  Unencodable
        # histories start stage 3 (the oracle pool) inside the feed.
        # End-of-input buckets dispatch largest-estimated-cost first
        # (BucketStream.finish — the per-run half of the daemon's
        # largest-cost-first scheduling).
        planners = {}  # id(ctx) -> (planner, BucketStream)
        for ctx, idx in dec.feed():
            st = planners.get(id(ctx))
            if st is None:
                planner = Planner(
                    ctx.model, spec=ctx.spec, slot_cap=slot_cap,
                    frontier=frontier, max_closure=max_closure,
                    max_dispatch=max_dispatch,
                    n_devices=n_devices,
                )
                st = planners[id(ctx)] = (planner, planner.open_stream())
            for pb in st[1].feed(ctx, idx):
                ex.submit(pb)
        # end-of-input buckets order by cost GLOBALLY across every
        # stream, not per stream: a decomposed run finishes with a
        # pass-through stream AND a sub-history stream, and a parent's
        # cost lives in the sum of its sub-bucket rows — finishing the
        # streams one after another would let a small early stream's
        # buckets under-schedule a high-fanout run's big sub-buckets
        # (the ROADMAP items 3+4 leftover).  finish() already sorts
        # within each stream; the stable global sort composes them.
        finished = []
        for planner, stream in planners.values():
            finished.extend(stream.finish())
            n_buckets += planner.n_buckets
            n_flushes += planner.n_flushes
        finished.sort(key=estimated_cost, reverse=True)
        for pb in finished:
            ex.submit(pb)
        ex.drain()

        # -- stage 3 drain: collect concurrent oracle verdicts
        dec.drain_oracles()

        if sp:
            # buckets = DISTINCT shape buckets (what the gauge reports);
            # flushes can exceed it when a bucket streams mid-input
            sp.set("buckets", n_buckets)
            sp.set("flushes", n_flushes)
            sp.set("chunks", ex.submitted)
            sp.set("peak-inflight", ex.peak_depth)
            sp.set("window", ex.window_size)
            sp.set("devices", ex.n_devices)
            if dec.n_decomposed:
                sp.set("decomposed", dec.n_decomposed)
                sp.set("partitions", dec.n_partitions)

    results = dec.results()
    if obs.enabled():
        if n_buckets:
            obs.gauge_max("jepsen_engine_bucket_count", n_buckets)
        finish_run_telemetry(results)

    return results
