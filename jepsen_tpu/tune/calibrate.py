"""The offline autotuner behind ``jepsen_tpu tune``.

Replaces the engine's hand-pinned dispatch constants with measured
picks for the *attached* device (ROADMAP item 4): a coordinate-descent
search (the schedule-fine-tuning shape of arXiv:2406.20037, sized for
our six-knob space) from the current defaults over

- ``union_mode`` — the dense subset-union lowering (this coordinate
  re-measures the unroll/gather gap per chip; ``matmul`` recasts the
  subset maps as one-hot MXU matmuls),
- ``closure_mode`` — fixed-round vs convergence-early-exit boolean
  closure in the Elle cycle screens (the sync cost of the early-exit
  ``while_loop`` only pays off at large vertex buckets),
- ``closure_impl`` — the closure squaring arithmetic (historical
  saturated-bf16 ``uint8`` planes, boolean-carry ``bf16`` MXU matmul,
  or the word-packed ``packed32`` boolean semiring whose budget caps
  price rows at W/n ≈ 1/32 of the uint8 footprint); crossed with
  ``closure_mode`` over the sweep's screen timings,
- ``window`` — the engine's in-flight dispatch bound,
- ``flush_rows`` — the streaming bucket flush threshold,
- ``row_bucket`` — the power-of-two dispatch-row floor,

each candidate timed as a full pipelined run (encode → bucket → window
→ drain, the production ``Planner``/``Executor`` composition) on
synthetic corpora covering both kernel routes.  Compile and execute
phases are read separately from the existing obs dispatch timings
(``jepsen_kernel_compile_seconds`` / ``_execute_seconds``), and the
objective is steady-state (execute-phase) wall time, so a candidate is
never penalized for the one-off jit of its first visit.

A second pass measures the **cost table**: per-(kernel, E, C, F)
dispatch seconds at several row counts — the measured stand-in for the
analytic proxy in ``planning.estimated_cost`` (the learned-TPU-cost
direction of arXiv:2008.01040, as a direct lookup table rather than a
trained predictor: the config space per shape bucket is small enough
to measure outright).

**Budget guardrail**: no proposal — sweep candidate or cost-table row
count — may put more per-chip rows in flight than the crash-calibrated
``fn.safe_dispatch`` cap.  :func:`proposal_within_budget` is the
single gate; rejected proposals are counted
(``jepsen_tune_budget_rejections_total``) and recorded in the sweep
diag, and every measured run's ``Executor.chip_row_accounting`` peaks
are re-checked after the fact (``budget_evidence``), so the artifact
carries proof, not a promise.

Results persist via :mod:`jepsen_tpu.tune.artifact`.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from .. import obs
from ..obs import journal as obs_journal
from . import artifact

#: sweep profiles: bounded candidate sets + corpus sizes.  "default"
#: fits the ~2-minute CPU-fallback budget; "smoke" is the tiny
#: make-check gate (seconds, not minutes).
PROFILES: Dict[str, Dict[str, Any]] = {
    # corpus shape matters: the sweep optimizes wall time ON ITS OWN
    # corpora, so these must look like production traffic (hundreds of
    # ops per history — the benchmark's CAS cells run 1000-op histories), or
    # a pick that wins at toy shapes loses at real ones (measured: an
    # L=40 sweep corpus picked a union mode 2× slower at L=200)
    "default": dict(
        n_hists=32, n_ops=160, n_procs=3, reps=2, passes=2,
        windows=(1, 2, 4, 8), unions=("unroll", "gather", "matmul"),
        closures=("fixed", "earlyexit"),
        impls=("uint8", "packed32", "bf16"),
        flush_rows=(4096, 16384, 65536), row_buckets=(32, 64, 128),
        cost_rows=(32, 128), screen_ns=(16, 64), n_graphs=24,
        budget_s=100.0,
    ),
    "smoke": dict(
        n_hists=10, n_ops=12, n_procs=3, reps=1, passes=1,
        windows=(1, 4), unions=("unroll", "gather", "matmul"),
        closures=("fixed", "earlyexit"),
        impls=("uint8", "packed32", "bf16"),
        flush_rows=(16384,), row_buckets=(64,),
        cost_rows=(8,), screen_ns=(16,), n_graphs=6, budget_s=30.0,
    ),
}

#: shared shape knobs for the synthetic corpora (small on purpose: the
#: tuner ranks configs, it does not need flagship batch sizes)
SLOT_CAP = 32
FRONTIER = 64


def proposal_within_budget(plan, rows: int, window: int,
                           n_devices: int = 1) -> bool:
    """True iff dispatching ``rows`` rows of ``plan`` under an
    in-flight ``window`` keeps per-chip concurrent rows within the
    crash-calibrated ``fn.safe_dispatch`` cap (``plan.disp``).  Dense
    kernels allow the full cap per dispatch at any depth (small
    per-row footprint — the measured flagship pattern); frontier
    kernels hold at most ``disp`` rows across the whole window (the
    executor splits chunks to ``disp//window``, or serializes when
    even that floors out).  A plan with no dispatchable kernel admits
    nothing.  ``plan.disp`` already carries the closure-impl pricing
    (``ops.cycles.cycles_max_dispatch``): a ``packed32`` screen plan's
    cap is ~32× the uint8 cap for the same shape, so word-packed
    candidates legally admit ~32× more rows per chunk under the same
    per-chip word budget."""
    if plan.fn is None or plan.disp == 0:
        return rows == 0
    cap = plan.disp * max(1, n_devices)
    if plan.kernel == "dense":
        return rows <= cap
    w = max(1, window)
    if plan.disp >= w:
        # window-deep frontier dispatch: w chunks of disp//w rows each
        # — total in flight ≤ disp per chip by construction
        return rows <= (plan.disp // w) * w * max(1, n_devices)
    return rows <= cap  # serialized: one full-cap dispatch at a time


@contextmanager
def _env(**kv):
    """Scoped environment overrides for the knobs the engine reads
    from the environment (union lowering, row-bucket floor)."""
    saved = {k: os.environ.get(k) for k in kv}
    try:
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _corpora(profile: Dict[str, Any]):
    """Synthetic measurement corpora: one dense-routed and one
    frontier-routed CAS-register batch (every history encodable, so
    timings are pure device+host pipeline, no oracle noise), plus a
    decomposable multi-register batch for the decomposed route's cost
    evidence, plus an ``"elle"`` list of encoded dependency graphs so
    the ``closure_mode`` coordinate has screen traffic to rank (NOT a
    ``(model, hists)`` pair — the history loops skip this key)."""
    import random

    from .. import models as m
    from ..synth import generate_history, generate_mr_history

    rng = random.Random(45100)
    n, L, P = profile["n_hists"], profile["n_ops"], profile["n_procs"]
    cas = [
        generate_history(rng, n_procs=P, n_ops=L, crash_p=0.0,
                         corrupt=(i % 4 == 0))
        for i in range(n)
    ]
    mr = [
        generate_mr_history(rng, n_procs=P, n_ops=L, n_keys=4,
                            n_values=4, crash_p=0.0, corrupt=(i % 4 == 0))
        for i in range(max(2, n // 4))
    ]
    return {
        "cas": (m.cas_register(0), cas),
        "multi-register": (m.multi_register({k: 0 for k in range(4)}), mr),
        "elle": _screen_corpus(profile.get("n_graphs", 8)),
    }


def _screen_corpus(n_graphs: int):
    """Deterministic encoded graphs for the screen timings: ring and
    chain relation matrices at the canonical no-suffix filter profile
    (the same shapes the cost-table cycles arm measures), spread over
    two vertex buckets so packed plane stacks of both shapes warm."""
    import numpy as np

    from ..elle import encode as encode_mod

    masks, nonadj = (1, 3, 7), ((4, 3),)
    encs = []
    for g in range(max(1, n_graphs)):
        n = 16 if g % 2 == 0 else 32
        rel = np.zeros((n, n), np.uint8)
        for i in range(n - 1):
            rel[i, i + 1] = (1, 2, 4)[(g + i) % 3]
        if g % 2 == 0:
            rel[n - 1, 0] = 1  # close into a ring
        encs.append(encode_mod.EncodedGraph(
            list(range(n)), rel, 7, masks, nonadj
        ))
    return encs


def _phase_seconds(reg) -> Tuple[float, float]:
    """(compile_s, execute_s) sums from the obs dispatch histograms —
    the existing per-dispatch timing seam, read instead of re-timed."""
    compile_s = execute_s = 0.0
    for d in reg.snapshot():
        if d["name"] == "jepsen_kernel_compile_seconds":
            compile_s += d.get("sum", 0.0)
        elif d["name"] == "jepsen_kernel_execute_seconds":
            execute_s += d.get("sum", 0.0)
    return compile_s, execute_s


def journal_rows(path: Optional[str] = None,
                 kernel: Optional[str] = None) -> List[dict]:
    """Production dispatch-journal rows
    (:mod:`jepsen_tpu.obs.journal`) read back in the cost-table entry
    shape — real-traffic evidence beside the synthetic
    :func:`measure_cost_table` points.  ``seconds`` is the warm
    execute time when the dispatch was a compile-cache hit, else the
    compile time; ``corpus`` is ``"journal"`` so consumers can tell
    measured-offline from observed-in-production rows.  Reads the
    process's configured journal by default (falling back to
    ``dispatch-journal.jsonl`` in the cwd); bad lines are skipped, a
    missing file is just an empty list."""
    p = path or obs_journal.path() or obs_journal.DEFAULT_FILENAME
    out: List[dict] = []
    for row in obs_journal.read_rows(p):
        if kernel is not None and row.get("kernel") != kernel:
            continue
        secs = (row["execute_s"] if row["cache"] == "hit"
                else row["compile_s"])
        out.append({
            "kernel": row["kernel"], "E": row["E"], "C": row["C"],
            "F": row["F"], "rows": row["rows"],
            "seconds": round(float(secs), 6),
            "corpus": "journal",
            "cache": row["cache"],
            "coalesced": row["coalesced"],
        })
    return out


class _Runner:
    """Measurement harness: one timed pipelined run per call, through
    the production planning/execution composition, with per-run budget
    evidence collected from the executor's chip-row accounting."""

    def __init__(self):
        self.budget_evidence: List[dict] = []
        self.budget_breaches: List[dict] = []

    def timed_run(self, model, hists, *, window: int, flush_rows: int,
                  max_closure: Optional[int] = None,
                  max_dispatch: Optional[int] = None) -> float:
        """Wall seconds of one full pipelined pass (encode → buckets →
        window → drain).  Oracle fallback is off: the corpora are fully
        encodable, and a worker pool would only add noise."""
        from ..engine import execution, planning

        ctx = planning.RunContext(model, hists, oracle_fallback=False)
        planner = planning.Planner(
            model, spec=ctx.spec, slot_cap=SLOT_CAP, frontier=FRONTIER,
            max_closure=max_closure, max_dispatch=max_dispatch,
            flush_rows=flush_rows,
        )
        ex = execution.Executor(window, max_dispatch=max_dispatch)
        t0 = time.perf_counter()
        for pb in planner.stream(ctx):
            ex.submit(pb)
        ex.drain()
        wall = time.perf_counter() - t0
        self._collect_budget(ex)
        return wall

    def timed_screens(self, encs, *, window: int, reps: int) -> float:
        """Wall seconds of one screen pass over encoded dependency
        graphs (best of ``reps`` after one un-timed warmup) — the
        traffic the ``closure_mode`` and ``closure_impl`` coordinates
        rank on (each candidate's screens run under its own
        mode × impl pair, so the sweep crosses the two axes).  Same
        production Executor, same budget evidence."""
        from ..engine import execution
        from ..ops import cycles as ops_cycles

        def one() -> float:
            ex = execution.Executor(window)
            t0 = time.perf_counter()
            ops_cycles.screen_graphs(encs, executor=ex)
            wall = time.perf_counter() - t0
            self._collect_budget(ex)
            return wall

        one()  # warmup: compiles
        return min(one() for _ in range(reps))

    def _collect_budget(self, ex) -> None:
        for acct in ex.chip_row_accounting.values():
            cap = acct["chip_cap"]
            if acct["kernel"] == "dense":
                cap = cap * ex.window_size
            ev = {
                "kernel": acct["kernel"],
                "peak_chip_rows": acct["peak_chip_rows"],
                "chip_cap": acct["chip_cap"],
                "window": ex.window_size,
                "within_budget": acct["peak_chip_rows"] <= cap,
            }
            self.budget_evidence.append(ev)
            if not ev["within_budget"]:  # engine invariant — loudly
                self.budget_breaches.append(ev)


def measure_config(runner: _Runner, corpora, cfg: Dict[str, Any],
                   reps: int) -> float:
    """Objective for one candidate config: steady-state wall seconds
    (best of ``reps`` after one un-timed warmup that absorbs compiles)
    across the dense- and frontier-routed corpora."""
    model, cas = corpora["cas"]
    total = 0.0
    with _env(JEPSEN_TPU_DENSE_UNION=cfg["union_mode"],
              JEPSEN_TPU_ENGINE_ROW_BUCKET=cfg["row_bucket"],
              JEPSEN_TPU_CYCLES_CLOSURE=cfg["closure_mode"],
              JEPSEN_TPU_CYCLES_IMPL=cfg["closure_impl"]):
        for max_closure in (None, 9):  # dense route, then frontier
            kw = dict(window=cfg["window"], flush_rows=cfg["flush_rows"],
                      max_closure=max_closure)
            runner.timed_run(model, cas, **kw)  # warmup: compiles
            total += min(
                runner.timed_run(model, cas, **kw) for _ in range(reps)
            )
        total += runner.timed_screens(
            corpora["elle"], window=cfg["window"], reps=reps
        )
    obs.count("jepsen_tune_measurements_total", phase="sweep")
    return total


def coordinate_descent(runner: _Runner, corpora, profile: Dict[str, Any],
                       deadline: float) -> Tuple[Dict[str, Any], dict]:
    """Start from the pinned defaults and improve one coordinate at a
    time, re-visiting until a full pass changes nothing (or the time
    budget runs out — the partial result is still valid: every visited
    config was really measured)."""
    from ..engine import execution, planning
    from ..ops import cycles as ops_cycles
    from ..ops import dense

    space = {
        "union_mode": tuple(profile["unions"]),
        "closure_mode": tuple(profile["closures"]),
        "closure_impl": tuple(profile["impls"]),
        "window": tuple(profile["windows"]),
        "flush_rows": tuple(profile["flush_rows"]),
        "row_bucket": tuple(profile["row_buckets"]),
    }
    current = {
        "union_mode": dense.DEFAULT_UNION,
        "closure_mode": ops_cycles.DEFAULT_CLOSURE_MODE,
        "closure_impl": ops_cycles.DEFAULT_CLOSURE_IMPL,
        "window": execution.DEFAULT_WINDOW,
        "flush_rows": planning.DEFAULT_FLUSH_ROWS,
        "row_bucket": execution.ROW_BUCKET,
    }
    reps = profile["reps"]
    scores: Dict[str, float] = {}
    trail: List[dict] = []
    truncated = False

    def key_of(cfg):
        return "|".join(f"{k}={cfg[k]}" for k in sorted(cfg))

    def score(cfg) -> float:
        k = key_of(cfg)
        if k not in scores:
            scores[k] = measure_config(runner, corpora, cfg, reps)
        return scores[k]

    best_s = score(current)
    for _pass in range(profile["passes"]):
        moved = False
        for coord, cands in space.items():
            for cand in cands:
                if time.perf_counter() > deadline:
                    truncated = True
                    break
                if cand == current[coord]:
                    continue
                trial = {**current, coord: cand}
                s = score(trial)
                trail.append({"coord": coord, "value": cand,
                              "seconds": round(s, 5)})
                if s < best_s:
                    current, best_s = trial, s
                    moved = True
            if truncated:
                break
        if truncated or not moved:
            break
    diag = {
        "best_seconds": round(best_s, 5),
        "measured_configs": len(scores),
        "trail": trail,
        "truncated": truncated,
    }
    return current, diag


# jt: timing — intentional dispatch-and-sync measurement loop
def measure_cost_table(runner: _Runner, corpora, profile: Dict[str, Any],
                       params: Dict[str, Any]) -> List[dict]:
    """Per-(kernel, E, C, F) dispatch seconds at bounded row counts —
    the interpolation points ``planning.estimated_cost`` serves.  Row
    proposals are clamped through :func:`proposal_within_budget`
    BEFORE any dispatch; an over-budget proposal is counted and
    dropped, never measured.  The inline ``block_until_ready`` syncs
    are the point — this IS a timing loop, not a dispatch path
    (annotated ``# jt: timing`` for the trace-safety pass)."""
    import jax.numpy as jnp
    import numpy as np

    from ..engine import planning

    entries: List[dict] = []
    with _env(JEPSEN_TPU_DENSE_UNION=params["union_mode"],
              JEPSEN_TPU_CYCLES_CLOSURE=params["closure_mode"],
              JEPSEN_TPU_CYCLES_IMPL=params["closure_impl"]):
        for name, pair in corpora.items():
            if name == "elle":
                continue  # encoded graphs, not (model, hists) — the
                # screen shapes get their own arm below
            model, hists = pair
            for max_closure in (None, 9):
                ctx = planning.RunContext(model, hists,
                                          oracle_fallback=False)
                planner = planning.Planner(
                    model, spec=ctx.spec, slot_cap=SLOT_CAP,
                    frontier=FRONTIER, max_closure=max_closure,
                )
                if planner.spec is None:
                    continue
                buckets, order = planner.encode_buckets(ctx)
                for key in order:
                    encs, tokens = buckets[key]
                    pb = planner.plan_rows(key, encs, tokens)
                    if pb is None or pb.plan.fn is None or pb.plan.disp == 0:
                        continue
                    plan = pb.plan
                    for rows in profile["cost_rows"]:
                        rows = min(rows, len(pb.rows))
                        if not proposal_within_budget(
                            plan, rows, params["window"]
                        ):
                            obs.count("jepsen_tune_budget_rejections_total")
                            continue
                        args = tuple(
                            jnp.asarray(np.asarray(a)[:rows])
                            for a in pb.arrays
                        )
                        out = plan.fn(*args)  # warmup: trace + compile
                        out[0].block_until_ready()
                        t0 = time.perf_counter()
                        out = plan.fn(*args)
                        out[0].block_until_ready()
                        secs = time.perf_counter() - t0
                        obs.count("jepsen_tune_measurements_total",
                                  phase="cost")
                        entries.append({
                            "kernel": plan.kernel, "E": plan.E,
                            "C": plan.C, "F": plan.frontier,
                            "rows": rows,
                            "seconds": round(secs, 6),
                            "corpus": name,
                        })
    # the Elle transactional screens: (kernel="cycles", E=n, C=0,
    # F=plane weight) rows — F counts the packed closure planes the
    # profile expands into on the batch axis — so the measured table
    # ranks screen buckets in the same seconds unit as history buckets
    # (the daemon's largest-cost-first ordering compares them
    # directly).  Deterministic ring/chain relation matrices at the
    # canonical no-suffix filter profile, under the swept closure mode.
    from ..ops import cycles as ops_cycles

    masks, nonadj = (1, 3, 7), ((4, 3),)
    with _env(JEPSEN_TPU_CYCLES_CLOSURE=params["closure_mode"],
              JEPSEN_TPU_CYCLES_IMPL=params["closure_impl"]):
        for n in profile.get("screen_ns", ()):
            plan = ops_cycles.ScreenPlan(n, masks, nonadj)
            if plan.disp == 0:
                continue
            for rows in profile["cost_rows"]:
                if not proposal_within_budget(plan, rows, params["window"]):
                    obs.count("jepsen_tune_budget_rejections_total")
                    continue
                rel = np.zeros((rows, n, n), np.uint8)
                for b in range(rows):
                    for i in range(n - 1):
                        rel[b, i, i + 1] = (1, 2, 4)[(b + i) % 3]
                    if b % 2 == 0:
                        rel[b, n - 1, 0] = 1  # close into a ring
                args = jnp.asarray(rel)
                out = plan.fn(args)  # warmup: trace + compile
                out[0].block_until_ready()
                t0 = time.perf_counter()
                out = plan.fn(args)
                out[0].block_until_ready()
                secs = time.perf_counter() - t0
                obs.count("jepsen_tune_measurements_total", phase="cost")
                entries.append({
                    "kernel": "cycles", "E": n, "C": 0,
                    "F": plan.frontier,
                    "rows": rows, "seconds": round(secs, 6),
                    "corpus": "elle-screen",
                })
    # one point per (kernel, E, C, F, rows): keep the fastest (least
    # noisy) observation when corpora overlap in shape
    best: Dict[tuple, dict] = {}
    for e in entries:
        k = (e["kernel"], e["E"], e["C"], e["F"], e["rows"])
        if k not in best or e["seconds"] < best[k]["seconds"]:
            best[k] = e
    return [best[k] for k in sorted(best)]


def run_tune(out_path: str = artifact.DEFAULT_PATH,
             profile: str = "default",
             budget_s: Optional[float] = None,
             activate: bool = True) -> Tuple[str, dict]:
    """The whole offline pass: sweep → cost table → persisted
    artifact.  Returns ``(path, artifact_dict)``; with ``activate``
    the fresh artifact becomes this process's active calibration."""
    prof = dict(PROFILES[profile])
    if budget_s is not None:
        prof["budget_s"] = float(budget_s)
    t_start = time.perf_counter()
    deadline = t_start + prof["budget_s"]
    device_kind, n_devices = artifact.device_key()
    corpora = _corpora(prof)
    runner = _Runner()

    params, sweep_diag = coordinate_descent(runner, corpora, prof, deadline)
    cost_table = measure_cost_table(runner, corpora, prof, params)
    if runner.budget_breaches:
        raise RuntimeError(
            "tuner measured a per-chip budget breach (engine invariant "
            f"violated): {runner.budget_breaches[:3]}"
        )
    sweep_diag.update({
        "profile": profile,
        "device_kind": device_kind,
        "n_devices": n_devices,
        "budget_checks": len(runner.budget_evidence),
        "budget_breaches": 0,
        "wall_s": round(time.perf_counter() - t_start, 3),
    })
    obs.gauge_set("jepsen_tune_sweep_seconds",
                  time.perf_counter() - t_start)
    import datetime

    data = artifact.build_artifact(
        params, cost_table, device_kind, n_devices,
        created_at=datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        sweep=sweep_diag,
    )
    artifact.save(data, out_path)
    if activate:
        artifact.set_active(artifact.Calibration(data))
    return out_path, data
