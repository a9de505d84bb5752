"""Batched cycle detection and transactional screens on the accelerator.

Dependency graphs become dense matrices; transitive closure by log₂(N)
rounds of boolean matrix squaring — each round one batched matmul,
which XLA tiles straight onto the MXU in bfloat16.  Three kernel
families share that core:

- **has-cycle** (:func:`has_cycle_batch`): a graph is cyclic iff its
  closure has a true diagonal — the boolean screen the rw-register
  per-key version graphs ride (SURVEY.md §7 step 8).
- **SCC membership screens** (:func:`_screen_fn` members): per-vertex
  forward×backward closure intersection — ``member[v] = ∃j r[v,j] ∧
  r[j,v]`` — computed per relation-filter mask of the Elle classify
  ladder (``ww`` for G0, ``ww|wr`` for G1c, ``+rw`` for G2, the
  process/realtime-suffixed variants), so ``elle.cycles.classify``
  only pays CPU Tarjan + BFS witness search on graphs (and ladder
  rungs) the device has already proven cyclic *under that filter*.
- **nonadjacent-rw walk screens** (:func:`_screen_fn` walks): closure
  over the 2n×2n lifted product graph (state = vertex × last-edge-was-
  rw) decides exactly whether a closed walk with no two cyclically
  adjacent rw edges exists through each vertex — the screening
  question of the snapshot-isolation cycle test (Adya G-SI); no walk
  anywhere means ``find_nonadjacent_cycle`` would answer None for
  every SCC, so the whole rung is skippable.

**Plane packing** (the peak-FLOP closure work): one screen dispatch no
longer pays a separate log₂(n)-round closure per ladder filter — the F
filter masks expand on-device into a ``(B·F, n, n)`` plane stack and
the Q lifted walk queries into ``(B·Q, 2n, 2n)``, then ONE
:func:`_bool_closure` runs per shape family.  A 5-rung screen bucket
therefore lowers to ~log₂(n) large batched matmuls instead of
5·log₂(n) small ones (pinned by the jaxpr ``dot_general``-count
regression test); the per-plane arithmetic is untouched, so results
stay byte-identical to the per-mask lowering (``make kernels-smoke``).

**Closure modes**: :func:`_bool_closure` either runs the full fixed
log₂(n) squaring ladder (``"fixed"``, a ``lax.scan``) or stops at
fixpoint (``"earlyexit"``, a ``lax.while_loop`` — byte-identical by
construction since post-fixpoint squarings are the identity on the
saturated {0,1} lattice).  The mode is a tuned engine knob
(``JEPSEN_TPU_CYCLES_CLOSURE`` > calibration ``closure_mode`` >
:data:`DEFAULT_CLOSURE_MODE`; doc/tuning.md) because the convergence
check is a device-wide sync whose cost only pays off at large n.
Rounds actually run come back as a per-row output and settle into
``jepsen_cycles_closure_rounds_total`` / ``_rounds_saved_total``.

**Closure implementations**: orthogonal to the mode, the squaring
*arithmetic* is a second tuned knob (``JEPSEN_TPU_CYCLES_IMPL`` >
calibration ``closure_impl`` > :data:`DEFAULT_CLOSURE_IMPL`):
``"uint8"`` is the historical saturated-bfloat16 lowering over the
uint8 relation planes (1 live bit per lane), ``"bf16"`` keeps a
boolean carry and casts to bfloat16 only for each round's MXU matmul
(threshold > 0), and ``"packed32"`` bit-packs adjacency rows into
uint32 words — :func:`_pack_words`, ``W = ⌈n/32⌉`` — and squares in
the boolean semiring as an AND-broadcast + OR-reduce over word lanes
(no popcount: reachability only cares about any-bit).  All three run
the identical closure recurrence on the same {0,1} lattice, so
members/walks/rounds are byte-identical by construction (the
kernels-smoke and fuzz gates pin it); what changes is density — the
packed stack moves W/n ≈ 1/32 of the uint8 bytes, so the budget math
(:func:`cycles_max_dispatch`, the plane-weight ``frontier``) prices
packed rows 32× cheaper and a packed bucket legally dispatches ~32×
more rows per chunk (doc/checker-engines.md "Word-packed closure").

Since the engine-routing work these kernels no longer dispatch through
a private loop: every batch is planned into :class:`CyclePlan` /
:class:`ScreenPlan` buckets (power-of-two vertex buckets ×
filter-profile, stacked ``(B, n, n)`` uint8 relation matrices — see
:mod:`jepsen_tpu.elle.encode`) and submitted through the production
:class:`~jepsen_tpu.engine.execution.Executor`: the bounded
``DispatchWindow``, the per-chip ``safe_dispatch`` row budget
(:func:`cycles_max_dispatch`, the crash-avoidance analogue of
``FRONTIER_DISPATCH_BUDGET``), mesh ``shard_map`` dispatch, and the
``(kernel="cycles", E=n, C=0, F=plane-weight)`` rows of the tune cost
table all apply to Elle traffic exactly as they do to history
checking (``F`` is the packed plane weight — one n×n plane per filter
mask plus four per lifted query; :func:`jepsen_tpu.elle.encode.plane_weight`).
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import dense


def _bucket(n: int) -> int:
    """Pad sizes to powers of two (min 16) to bound recompiles."""
    return max(16, 1 << (n - 1).bit_length())


#: compiled-closure cache bound: buckets are powers of two ≥ 16
#: (2^4, 2^5, …), so 32 distinct entries cover every size to 2^35
#: vertices — far past anything dispatchable — while adversarial size
#: streams (one graph per power of two, forever) can no longer leak
#: compiled executables without limit the way ``maxsize=None`` did
CLOSURE_CACHE_SIZE = 32

#: per-dispatch footprint budget for the cycle kernels, in bf16 words
#: of live closure state — the crash-avoidance analogue of
#: ``wgl.FRONTIER_DISPATCH_BUDGET`` for the matrix-closure family.
#: The packed screen stack holds 2 n² words per row per filter plane
#: (adjacency + closure) and 8 n² per lifted nonadjacent plane (the
#: 2n×2n product graph); 16M words keeps every measured-good
#: elle_bench shape (B=4096 × n=16 … B=256 × n=256) dispatchable in
#: ≤2 chunks while bounding in-flight HBM the same way the engine
#: bounds history kernels — ``has_cycle_batch`` historically had NO
#: such cap, so a huge graph batch could exceed the per-chip budget
#: the engine enforces everywhere else (the PR's pinned regression).
CYCLES_DISPATCH_BUDGET = 16_777_216

#: largest row count per dispatch, shared ceiling with the engine
DEFAULT_CYCLES_MAX_DISPATCH = 16384

#: closure-iteration lowering when neither the environment nor a
#: calibration picks one: the fixed log₂(n) scan — the earlyexit
#: while_loop's fixpoint test is a device-wide sync per round, a cost
#: the tuner must measure before opting in (doc/tuning.md)
DEFAULT_CLOSURE_MODE = "fixed"

_VALID_CLOSURE_MODES = ("fixed", "earlyexit")

#: closure squaring arithmetic when neither the environment nor a
#: calibration picks one: the historical saturated-bf16 lowering over
#: uint8 planes — packed32's unpack/repack and bf16's per-round cast
#: are real per-round costs the tuner must measure before opting in
DEFAULT_CLOSURE_IMPL = "uint8"

_VALID_CLOSURE_IMPLS = ("uint8", "packed32", "bf16")


def closure_mode() -> str:
    """Resolved closure-iteration mode for the cycle kernels:
    ``JEPSEN_TPU_CYCLES_CLOSURE`` > active calibration
    (``closure_mode`` param — ``jepsen_tpu tune`` measures the
    fixed/earlyexit gap per chip) > :data:`DEFAULT_CLOSURE_MODE`.
    Part of every closure-kernel cache key, so flipping it can never
    serve a stale lowering."""
    from ..tune import artifact as _cal

    def parse(v: str):
        v = v.strip().lower()
        return v if v in _VALID_CLOSURE_MODES else None

    return _cal.resolve_knob(
        "JEPSEN_TPU_CYCLES_CLOSURE",
        parse,
        lambda cal: cal.closure_mode(),
        DEFAULT_CLOSURE_MODE,
    )


def closure_impl() -> str:
    """Resolved closure-squaring arithmetic for the cycle kernels:
    ``JEPSEN_TPU_CYCLES_IMPL`` > active calibration (``closure_impl``
    param — ``jepsen_tpu tune`` measures the uint8/packed32/bf16 gap
    per chip and shape) > :data:`DEFAULT_CLOSURE_IMPL`.  Part of every
    closure-kernel cache key (and of the mesh ``shard_fn`` key), so
    flipping it can never serve a stale lowering."""
    from ..tune import artifact as _cal

    def parse(v: str):
        v = v.strip().lower()
        return v if v in _VALID_CLOSURE_IMPLS else None

    return _cal.resolve_knob(
        "JEPSEN_TPU_CYCLES_IMPL",
        parse,
        lambda cal: cal.closure_impl(),
        DEFAULT_CLOSURE_IMPL,
    )


def _pack_words(adj):
    """Device word-packing: ``(..., n) bool → (..., W) uint32`` with
    lane ``j`` at word ``j // 32``, bit ``j % 32`` — bit-for-bit the
    little-order layout of the host
    :func:`jepsen_tpu.ops.dense.pack_words_np` (the round-trip
    property tests pin the two equal).  The weighted sum over 32-lane
    groups is exact: distinct powers of two never carry."""
    n = adj.shape[-1]
    W = dense.word_count(n)
    lanes = adj.astype(jnp.uint32)
    pad = W * dense.WORD_LANES - n
    if pad:
        lanes = jnp.pad(lanes, [(0, 0)] * (lanes.ndim - 1) + [(0, pad)])
    weights = jnp.uint32(1) << jnp.arange(
        dense.WORD_LANES, dtype=jnp.uint32
    )
    return jnp.sum(
        lanes.reshape(lanes.shape[:-1] + (W, dense.WORD_LANES)) * weights,
        axis=-1,
        dtype=jnp.uint32,
    )


def _unpack_words(words, n: int):
    """Inverse of :func:`_pack_words`: ``(..., W) uint32 → (..., n)``
    bool; lanes past ``n`` are word-floor padding and are dropped."""
    shifts = jnp.arange(dense.WORD_LANES, dtype=jnp.uint32)
    lanes = (words[..., None] >> shifts) & jnp.uint32(1)
    return lanes.reshape(words.shape[:-1] + (-1,))[..., :n] > 0


def closure_rounds(n: int) -> int:
    """Squaring rounds that guarantee full transitive closure of an
    n-vertex graph (path length doubles per round)."""
    return max(1, math.ceil(math.log2(max(2, n))))


def cycles_max_dispatch(
    n: int,
    n_filters: int = 1,
    n_lifted: int = 0,
    max_dispatch: Optional[int] = None,
    impl: str = "uint8",
) -> int:
    """Largest safe per-dispatch row count for a cycle kernel over
    ``n``-vertex graphs whose packed stack carries ``n_filters``
    membership planes and ``n_lifted`` lifted (2n×2n) walk planes per
    row.  Returns 0 when even a single row exceeds the budget —
    callers must route those graphs to the CPU path instead of
    dispatching.

    ``impl="packed32"`` prices each plane at its word-packed footprint
    — ``n·W`` uint32 words (``W = ⌈n/32⌉``, the lifted planes at
    ``2n·⌈2n/32⌉``) instead of ``n²`` lanes, i.e. W/n ≈ 1/32 of the
    uint8 footprint — so a packed bucket legally dispatches ~32× more
    rows per chunk under the same :data:`CYCLES_DISPATCH_BUDGET`
    (``"bf16"`` keeps the uint8 pricing: its carry is still one lane
    per vertex pair)."""
    if max_dispatch is None:
        max_dispatch = DEFAULT_CYCLES_MAX_DISPATCH
    if impl == "packed32":
        per_row = (2 * n * dense.word_count(n) * max(1, n_filters)
                   + 2 * (2 * n) * dense.word_count(2 * n) * n_lifted)
    else:
        per_row = n * n * (2 * max(1, n_filters) + 8 * n_lifted)
    if per_row > CYCLES_DISPATCH_BUDGET:
        return 0
    return max(1, min(max_dispatch, CYCLES_DISPATCH_BUDGET // per_row))


def _bool_closure(adj, mode: str = "fixed", impl: str = "uint8"):
    """Transitive (≥1 step) boolean closure by rounds of matrix
    squaring; shape-static, trace-safe.  Returns
    ``(closure bool, rounds-run int32 scalar)``.

    ``mode="fixed"`` always runs the full log₂(n) ladder as a
    ``lax.scan``; ``mode="earlyexit"`` wraps the same squaring step in
    a ``lax.while_loop`` that stops once a round changes nothing.
    Byte-identical by construction: the squaring step is monotone and
    idempotent at fixpoint on the saturated {0,1} values, so the extra
    rounds the fixed ladder runs past convergence are the identity.

    ``impl`` picks the squaring arithmetic (module docstring "Closure
    implementations"): ``"uint8"`` the historical saturated-bf16
    carry, ``"bf16"`` a boolean carry with a per-round bf16 MXU matmul
    thresholded > 0, ``"packed32"`` a uint32 word carry
    (:func:`_pack_words`) squared in the boolean semiring — one round
    is an AND-broadcast of row lanes against the word rows plus an
    OR-reduce over the intermediate-vertex axis, no popcount.  All
    three run the same recurrence ``r ← r ∪ r·r`` on the same lattice,
    so closures AND fixpoint round counts are byte-identical across
    impls (the fuzz gate pins diameters 1..n)."""
    n = adj.shape[-1]
    rounds = closure_rounds(n)

    if impl == "packed32":
        def square(rc):  # rc: (..., n, W) uint32 word rows
            lanes = _unpack_words(rc, n)  # (..., n, n): i reaches k?
            hops = jnp.bitwise_or.reduce(
                jnp.where(lanes[..., None], rc[..., None, :, :],
                          jnp.uint32(0)),
                axis=-2,
            )
            return rc | hops

        rw = _pack_words(adj)
        if mode == "earlyexit":
            def cond(carry):
                _, changed, i = carry
                return changed & (i < rounds)

            def body(carry):
                rc, _, i = carry
                rr = square(rc)
                return rr, jnp.any(rr != rc), i + jnp.int32(1)

            rw, _, used = jax.lax.while_loop(
                cond, body, (rw, jnp.bool_(True), jnp.int32(0))
            )
            return _unpack_words(rw, n), used

        def step(rc, _):
            return square(rc), None

        rw, _ = jax.lax.scan(step, rw, None, length=rounds)
        return _unpack_words(rw, n), jnp.int32(rounds)

    if impl == "bf16":
        def square_b(rb):  # rb: (..., n, n) bool carry
            f = rb.astype(jnp.bfloat16)
            return rb | (jnp.matmul(f, f) > 0)

        rb = adj > 0 if adj.dtype != jnp.bool_ else adj
        if mode == "earlyexit":
            def cond(carry):
                _, changed, i = carry
                return changed & (i < rounds)

            def body(carry):
                rc, _, i = carry
                rr = square_b(rc)
                return rr, jnp.any(rr != rc), i + jnp.int32(1)

            rb, _, used = jax.lax.while_loop(
                cond, body, (rb, jnp.bool_(True), jnp.int32(0))
            )
            return rb, used

        def step(rc, _):
            return square_b(rc), None

        rb, _ = jax.lax.scan(step, rb, None, length=rounds)
        return rb, jnp.int32(rounds)

    r = adj.astype(jnp.bfloat16)

    if mode == "earlyexit":
        def cond(carry):
            _, changed, i = carry
            return changed & (i < rounds)

        def body(carry):
            rc, _, i = carry
            rr = jnp.clip(rc + jnp.matmul(rc, rc), 0.0, 1.0)
            return rr, jnp.any(rr != rc), i + jnp.int32(1)

        r, _, used = jax.lax.while_loop(
            cond, body, (r, jnp.bool_(True), jnp.int32(0))
        )
        return r > 0.0, used

    def step(rc, _):
        # r ∪ r·r, saturated to {0,1}; stays in bfloat16 for the MXU
        rr = jnp.clip(rc + jnp.matmul(rc, rc), 0.0, 1.0)
        return rr, None

    r, _ = jax.lax.scan(step, r, None, length=rounds)
    return r > 0.0, jnp.int32(rounds)


@lru_cache(maxsize=CLOSURE_CACHE_SIZE)
def _closure_fn(n: int, mode: str = "fixed", impl: str = "uint8"):  # jt: allow[budget-missing-cap] — capped by the engine-facing wrapper _cyclic_fn
    @jax.jit
    def has_cycle(adj):  # adj: (B, n, n) bool
        r, used = _bool_closure(adj, mode, impl)
        diag = jnp.diagonal(r, axis1=-2, axis2=-1)
        flags = jnp.any(diag, axis=-1)
        return flags, jnp.broadcast_to(used, flags.shape)

    has_cycle.closure_mode = mode  # rides the mesh shard_fn cache key
    has_cycle.closure_impl = impl
    return has_cycle


@lru_cache(maxsize=CLOSURE_CACHE_SIZE)
def _cyclic_fn(n: int, mode: str = "fixed", impl: str = "uint8"):  # jt: jaxpr(dot_generals<=log2n+2, dtype[uint8]=bfloat16, dtype[packed32]=uint32, dtype[bf16]=bool, budget=0.2..0.6)
    """Engine-facing variant of :func:`_closure_fn`: tuple outputs (the
    execution layer materializes output *tuples* — flags plus the
    per-row rounds-run evidence) and a ``safe_dispatch`` row cap like
    every other engine kernel."""
    base = _closure_fn(n, mode, impl)
    fn = jax.jit(lambda adj: base(adj))
    fn.safe_dispatch = cycles_max_dispatch(n, 1, 0, impl=impl)
    fn.closure_mode = mode  # both knobs ride the mesh shard_fn cache key
    fn.closure_impl = impl
    return fn


def _screen_fn(n: int, masks: Tuple[int, ...],
               nonadj: Tuple[Tuple[int, int], ...]):
    """The production transactional-screen kernel: the packed lowering
    at the resolved :func:`closure_mode` / :func:`closure_impl` (see
    :func:`_screen_fn_variant` for the cache and the per-mask
    reference lowering)."""
    return _screen_fn_variant(n, masks, nonadj, True, closure_mode(),
                              closure_impl())


@lru_cache(maxsize=CLOSURE_CACHE_SIZE)
def _screen_fn_variant(n: int, masks: Tuple[int, ...],  # jt: jaxpr(dot_generals<=2*log2n+3, dtype[uint8]=bfloat16, dtype[packed32]=uint32, dtype[bf16]=bool, budget=0.1..0.35)
                       nonadj: Tuple[Tuple[int, int], ...],
                       packed: bool, mode: str, impl: str = "uint8"):
    """The transactional screen kernel for ``n``-vertex graphs: per
    relation-filter SCC membership masks plus per-(want, rest) lifted
    nonadjacent-walk masks, all in ONE dispatch over a ``(B, n, n)``
    uint8 relation-bit batch (bit assignment:
    ``jepsen_tpu.elle.encode.REL_BITS``).  Returns
    ``(members: (B, F, n) bool, walks: (B, Q, n) bool,
    rounds: (B,) int32)`` — rounds is the closure-squaring count the
    dispatch actually ran (broadcast per row; settle turns it into the
    rounds/rounds-saved counters).

    ``packed=True`` (production) folds the F filter planes into the
    batch axis as a ``(B·F, n, n)`` stack and the Q lifted queries as
    ``(B·Q, 2n, 2n)``, running ONE :func:`_bool_closure` per shape
    family — ~log₂(n) large batched matmuls for the whole ladder.
    ``packed=False`` keeps the historical per-mask loop (F + Q small
    closures) as the differential reference the equality gates compare
    against; both produce byte-identical members/walks because batched
    matmul is independent per batch element.  ``impl`` selects the
    closure squaring arithmetic (:func:`closure_impl`); it only
    touches :func:`_bool_closure` internals, so every
    (packed, mode, impl) combination screens identically."""
    F, Q = len(masks), len(nonadj)

    @jax.jit
    def screen(rel):  # rel: (B, n, n) uint8
        B = rel.shape[0]
        used = jnp.int32(0)
        if packed:
            if masks:
                marr = jnp.asarray(masks, jnp.uint8)
                planes = (rel[:, None] & marr[None, :, None, None]) > 0
                c, um = _bool_closure(planes.reshape(B * F, n, n), mode, impl)
                c = c.reshape(B, F, n, n)
                # v sits on a cycle of this filtered subgraph iff some
                # j is reachable forward AND backward (j = v covers
                # self loops, which the graph layer already drops)
                m = jnp.any(c & jnp.swapaxes(c, -1, -2), axis=-1)
                used = used + um
            else:
                m = jnp.zeros((B, 0, n), bool)
            if nonadj:
                wants = jnp.asarray([wq for wq, _ in nonadj], jnp.uint8)
                rests = jnp.asarray([rq for _, rq in nonadj], jnp.uint8)
                aw = (rel[:, None] & wants[None, :, None, None]) > 0
                ar = (rel[:, None] & rests[None, :, None, None]) > 0
                # lifted product graph over (vertex, last-edge-was-
                # want): a want edge is only traversable from state 0
                # (previous edge not want) and lands in state 1; rest
                # edges land in state 0 from either.  A closed walk
                # u →want→ w →…→ (u, state 0) is exactly a walk whose
                # want edges are never cyclically adjacent (the
                # closing rest edge precedes the forced first want
                # edge in the rotation).
                top = jnp.concatenate([ar, aw], axis=-1)
                bot = jnp.concatenate([ar, jnp.zeros_like(ar)], axis=-1)
                lifted = jnp.concatenate([top, bot], axis=-2)
                c, uw = _bool_closure(
                    lifted.reshape(B * Q, 2 * n, 2 * n), mode, impl
                )
                c = c.reshape(B, Q, 2 * n, 2 * n)
                reach = c[:, :, n:, :n]  # from (·, 1) to (·, 0), ≥1 step
                w = jnp.any(aw & jnp.swapaxes(reach, -1, -2), axis=-1)
                used = used + uw
            else:
                w = jnp.zeros((B, 0, n), bool)
        else:
            members = []
            for mask in masks:
                r, u = _bool_closure((rel & jnp.uint8(mask)) > 0, mode, impl)
                members.append(
                    jnp.any(r & jnp.swapaxes(r, -1, -2), axis=-1)
                )
                used = used + u
            walks = []
            for want, rest in nonadj:
                aw = (rel & jnp.uint8(want)) > 0
                ar = (rel & jnp.uint8(rest)) > 0
                top = jnp.concatenate([ar, aw], axis=-1)
                bot = jnp.concatenate([ar, jnp.zeros_like(ar)], axis=-1)
                c, u = _bool_closure(
                    jnp.concatenate([top, bot], axis=-2), mode, impl
                )
                reach = c[:, n:, :n]
                walks.append(
                    jnp.any(aw & jnp.swapaxes(reach, -1, -2), axis=-1)
                )
                used = used + u
            m = (jnp.stack(members, axis=1) if members
                 else jnp.zeros((B, 0, n), bool))
            w = (jnp.stack(walks, axis=1) if walks
                 else jnp.zeros((B, 0, n), bool))
        rounds = jnp.broadcast_to(used, (B,)).astype(jnp.int32)
        return m, w, rounds

    screen.safe_dispatch = cycles_max_dispatch(n, F, Q, impl=impl)
    screen.closure_mode = mode  # both knobs ride the mesh shard_fn cache key
    screen.closure_impl = impl
    return screen


def _run_elle(fn, mesh, rel, n_out: int):
    """Dispatch one stacked relation batch, sharded when a mesh is
    resident (the executor hands us device-multiple row counts)."""
    if mesh is None:
        return fn(jnp.asarray(rel))
    from ..parallel import mesh as mesh_mod

    return mesh_mod.sharded_elle(fn, mesh, rel, n_out)


def _settle_closure_obs(plan, rounds: np.ndarray, n_live: int) -> None:
    """Record one settled dispatch's closure evidence: rounds actually
    run vs the plan's full ladder (the earlyexit savings — identically
    zero under ``"fixed"``), and the packed-plane batch occupancy
    (live planes / dispatched planes; padding rows are the only dead
    planes, so the ratio equals live rows / padded rows)."""
    from .. import obs

    if not obs.enabled() or rounds.size == 0:
        return
    live = rounds[: max(1, n_live)]
    used = int(live.max())
    obs.count("jepsen_cycles_closure_rounds_total", used,
              mode=plan.closure_mode)
    obs.count("jepsen_cycles_closure_rounds_saved_total",
              max(0, plan.rounds_full - used), mode=plan.closure_mode)
    obs.gauge_set("jepsen_cycles_packed_plane_occupancy",
                  n_live / rounds.shape[0])
    # which squaring arithmetic actually dispatched (the tuner settles
    # the winner per shape; this is the evidence it actually ran)
    obs.count("jepsen_cycles_impl_total", 1, impl=plan.closure_impl)
    if plan.closure_impl == "packed32":
        # live vertex lanes / carried word lanes: 1.0 on word-floored
        # buckets, < 1 only when a caller bypasses encode.graph_bucket
        obs.gauge_set(
            "jepsen_cycles_word_lane_occupancy",
            plan.E / (dense.word_count(plan.E) * dense.WORD_LANES),
        )
    # estimated MXU work this dispatch actually ran: each round squares
    # every live row's packed plane stack (~2·E³ flops per E-plane;
    # the lifted 2E-planes ride the plan's frontier weight), so the
    # closure FLOP-rate can be read without re-deriving shapes
    obs.count("jepsen_cycles_closure_flops_total",
              int(2.0 * float(plan.E) ** 3 * plan.frontier * used
                  * max(1, n_live)),
              mode=plan.closure_mode)


class ScreenResult:
    """One graph's device screens, bucket-width: ``members[mask]`` and
    ``walks[(want, rest)]`` are per-vertex bool arrays over the padded
    bucket (callers slice by their own vertex count/order)."""

    __slots__ = ("members", "walks")

    def __init__(self, members, walks):
        self.members = members
        self.walks = walks


class CyclePlan:
    """Executor-conforming plan for the boolean has-cycle screen: one
    uint8/bool adjacency input, one cyclic-flag output per row (plus
    the rounds-run evidence).  Row tokens are ``(sink, idx)`` — settle
    writes ``sink[idx]``."""

    kernel = "cycles"
    #: neutral pad rows are all-zero relation matrices — edge-free,
    #: hence acyclic, hence invisible to every screen (the executor
    #: pads with these; the plan owns the convention, never borrowing
    #: the history kernels' 6-array fills)
    pad_fills = (0,)
    __slots__ = ("fn", "disp", "E", "C", "frontier", "closure_mode",
                 "closure_impl", "rounds_full")

    def __init__(self, n: int, max_dispatch: Optional[int] = None):
        mode = closure_mode()
        impl = closure_impl()
        self.closure_mode = mode
        self.closure_impl = impl
        self.fn = _cyclic_fn(n, mode, impl)
        self.E, self.C, self.frontier = n, 0, 1
        self.rounds_full = closure_rounds(n)
        self.disp = cycles_max_dispatch(n, 1, 0, max_dispatch, impl)

    def run_rows(self, mesh, arrays):
        return _run_elle(self.fn, mesh, arrays[0], 2)

    def settle_rows(self, rows, mat, n_live: int) -> None:
        flags = np.asarray(mat[0])[:n_live]
        _settle_closure_obs(self, np.asarray(mat[1]), n_live)
        for row, (sink, idx) in enumerate(rows):
            sink[idx] = bool(flags[row])


class ScreenPlan:
    """Executor-conforming plan for the full transactional screen of
    one (vertex bucket, filter profile): settle hands each row token's
    sink a :class:`ScreenResult` keyed by the profile's masks.  The
    cost-table/proxy ``frontier`` axis is the packed plane weight —
    the batch-axis expansion factor of the one-closure lowering."""

    kernel = "cycles"
    pad_fills = (0,)  # see CyclePlan.pad_fills
    __slots__ = ("fn", "disp", "E", "C", "frontier", "masks", "nonadj",
                 "closure_mode", "closure_impl", "rounds_full")

    def __init__(self, n: int, masks: Tuple[int, ...],
                 nonadj: Tuple[Tuple[int, int], ...],
                 max_dispatch: Optional[int] = None):
        from ..elle import encode as encode_mod

        self.masks = tuple(masks)
        self.nonadj = tuple(nonadj)
        mode = closure_mode()
        impl = closure_impl()
        self.closure_mode = mode
        self.closure_impl = impl
        self.fn = _screen_fn_variant(n, self.masks, self.nonadj, True,
                                     mode, impl)
        self.E, self.C = n, 0
        self.frontier = encode_mod.plane_weight(self.masks, self.nonadj,
                                                impl)
        self.rounds_full = (
            (closure_rounds(n) if self.masks else 0)
            + (closure_rounds(2 * n) if self.nonadj else 0)
        )
        self.disp = cycles_max_dispatch(
            n, len(self.masks), len(self.nonadj), max_dispatch, impl
        )

    def run_rows(self, mesh, arrays):
        return _run_elle(self.fn, mesh, arrays[0], 3)

    def settle_rows(self, rows, mat, n_live: int) -> None:
        members = np.asarray(mat[0])[:n_live]
        walks = np.asarray(mat[1])[:n_live]
        _settle_closure_obs(self, np.asarray(mat[2]), n_live)
        for row, (sink, idx) in enumerate(rows):
            sink[idx] = ScreenResult(
                {m: members[row, f] for f, m in enumerate(self.masks)},
                {q: walks[row, w] for w, q in enumerate(self.nonadj)},
            )


def _submit_elle_buckets(planned, window, executor):
    """Dispatch planned elle buckets through the production engine:
    largest estimated cost first (the same scheduling hook history
    buckets use), bounded window, per-chip budget, mesh — then drain
    and record the graphs-per-dispatch evidence."""
    from .. import obs
    from ..engine import execution, planning

    ex = executor if executor is not None else execution.Executor(window)
    planned.sort(key=planning.estimated_cost, reverse=True)
    sub0 = ex.submitted
    total_rows = 0
    for pb in planned:
        total_rows += len(pb.rows)
        ex.submit(pb)
    ex.drain()
    n_disp = ex.submitted - sub0
    if obs.enabled() and n_disp:
        obs.registry().histogram(
            "jepsen_elle_graphs_per_dispatch",
            buckets=(1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0, 16384.0),
        ).observe(total_rows / n_disp)


def _np_bool_closure(adj: np.ndarray) -> np.ndarray:
    """Vectorized host transitive closure: numpy boolean matmul
    squaring over an arbitrary leading batch shape
    (``(..., n, n) → (..., n, n)``) — the CPU mirror of
    :func:`_bool_closure`."""
    r = np.asarray(adj, dtype=bool)
    for _ in range(closure_rounds(r.shape[-1])):
        r = r | (r @ r)
    return r


def _np_has_cycle(adj: np.ndarray):
    """Host boolean-closure fallback for graphs past the dispatch
    budget (the engine must never dispatch a shape it cannot cap).
    Accepts one ``(n, n)`` matrix (→ bool) or a stacked ``(B, n, n)``
    batch (→ ``(B,)`` bool) — the batch form is one vectorized
    matmul-squaring ladder, not a per-matrix loop."""
    r = _np_bool_closure(adj)
    any_diag = np.diagonal(r, axis1=-2, axis2=-1).any(axis=-1)
    return any_diag if any_diag.ndim else bool(any_diag)


def _np_screen(rel: np.ndarray, masks: Sequence[int],
               nonadj: Sequence[Tuple[int, int]]):
    """Pure-numpy reference of the screen kernel: ``(B, n, n)`` uint8
    relation batch → ``(members (B, F, n), walks (B, Q, n))`` — the
    CPU parity oracle the packed/per-mask equality gates compare
    against (tests and ``make kernels-smoke``)."""
    rel = np.asarray(rel, np.uint8)
    B, n = rel.shape[0], rel.shape[-1]
    members = np.zeros((B, len(masks), n), bool)
    for f, mask in enumerate(masks):
        r = _np_bool_closure((rel & np.uint8(mask)) > 0)
        members[:, f] = (r & np.swapaxes(r, -1, -2)).any(axis=-1)
    walks = np.zeros((B, len(nonadj), n), bool)
    for q, (want, rest) in enumerate(nonadj):
        aw = (rel & np.uint8(want)) > 0
        ar = (rel & np.uint8(rest)) > 0
        top = np.concatenate([ar, aw], axis=-1)
        bot = np.concatenate([ar, np.zeros_like(ar)], axis=-1)
        c = _np_bool_closure(np.concatenate([top, bot], axis=-2))
        reach = c[:, n:, :n]
        walks[:, q] = (aw & np.swapaxes(reach, -1, -2)).any(axis=-1)
    return members, walks


#: host-fallback stacking bound, in words of resident state:
#: over-budget buckets batch through the word-packed numpy closure in
#: chunks of this many uint32 words so the fallback never materializes
#: an unbounded stack for the very shapes that were too big for the
#: device.  Historically the resident stack was (B, n, n) bool — one
#: word per LANE — so CPU-oracle parity on large n blew this budget
#: 32× earlier than the device path, whose budget counts packed words
#: (the PR's pinned n=1024 regression)
_NP_STACK_BUDGET = 1 << 26


def _np_chunk_rows(n: int) -> int:
    """Host-fallback chunk size for ``n``-vertex graphs: the resident
    stack is word-packed (``n·W`` uint32 words per row,
    :func:`jepsen_tpu.ops.dense.pack_words_np`) so the budget divides
    by ``n·W`` instead of the ``n²`` bools the unpacked stacking paid
    — 32× more rows per chunk at n = 1024."""
    return max(1, _NP_STACK_BUDGET // (n * dense.word_count(n)))


def _np_packed_closure(rw: np.ndarray, n: int) -> np.ndarray:
    """Word-packed host transitive closure: ``(B, n, W) uint32 →
    (B, n, W)`` closed, ``n`` a multiple of 32 (callers word-floor the
    pad; all-zero padding rows are edge-free, hence inert).  One
    squaring round ORs intermediate row ``k``'s word row into every
    row ``i`` whose packed lanes reach ``k``, grouped by bit position
    ``j`` (the intermediates ``k = 32·w + j`` live at one fixed bit of
    every word), so the transient is ``(B, n, W, W)`` uint32 — never
    the ``(B, n, n)`` bool plane the unpacked host closure
    materializes.  Fixpoint rounds short-circuit: the host path
    reports no rounds evidence, so stopping early is pure savings."""
    rw = np.array(rw, np.uint32, copy=True)
    for _ in range(closure_rounds(n)):
        sq = np.zeros_like(rw)
        for j in range(dense.WORD_LANES):
            # pj[b, i, w]: row i reaches intermediate k = 32·w + j?
            pj = ((rw >> np.uint32(j)) & np.uint32(1)).astype(bool)
            rj = rw[:, j::dense.WORD_LANES, :]  # (B, W, W): those rows
            sq |= np.bitwise_or.reduce(
                np.where(pj[..., None], rj[:, None, :, :],
                         np.uint32(0)),
                axis=2,
            )
        nxt = rw | sq
        if np.array_equal(nxt, rw):
            break
        rw = nxt
    return rw


def _np_packed_has_cycle(rw: np.ndarray, n: int) -> np.ndarray:
    """Cyclic flags for a word-packed ``(B, n, W)`` stack: closure in
    sub-blocks whose ``(blk, n, W, W)`` squaring transient stays under
    :data:`_NP_STACK_BUDGET`, then the packed diagonal test (bit
    ``i % 32`` of word ``i // 32`` on row ``i``)."""
    B, W = rw.shape[0], rw.shape[-1]
    blk = max(1, _NP_STACK_BUDGET // (n * W * W))
    flags = np.zeros(B, bool)
    idx = np.arange(n)
    shifts = (idx % dense.WORD_LANES).astype(np.uint32)
    for lo in range(0, B, blk):
        closed = _np_packed_closure(rw[lo:lo + blk], n)
        diag = (closed[:, idx, idx // dense.WORD_LANES] >> shifts) & 1
        flags[lo:lo + blk] = diag.any(axis=-1)
    return flags


def has_cycle_batch(
    mats: Sequence[np.ndarray],
    window: Optional[int] = None,
    executor=None,
    max_dispatch: Optional[int] = None,
) -> np.ndarray:
    """Which of these adjacency matrices contain a cycle?  Matrices
    bucket by padded size so one compile covers many shapes, and the
    buckets dispatch through the production engine
    :class:`~jepsen_tpu.engine.execution.Executor` — the bounded
    window (``window=None`` takes the engine default; 1 = the old
    strictly serial dispatch-sync loop), the per-chip
    :func:`cycles_max_dispatch` row budget (a huge batch chunks
    instead of exceeding the HBM bound the engine enforces for every
    other kernel), and mesh sharding when a slice is resident.
    ``executor=`` lets a resident owner (the serve daemon, smoke
    checks) supply its own."""
    from ..engine import planning

    out = np.zeros(len(mats), dtype=bool)
    by_bucket: dict = {}
    order: List[int] = []
    for i, m in enumerate(mats):
        n = _bucket(max(1, m.shape[0]))
        if n not in by_bucket:
            by_bucket[n] = []
            order.append(n)
        by_bucket[n].append(i)

    planned = []
    for n in order:
        idxs = by_bucket[n]
        plan = CyclePlan(n, max_dispatch)
        if plan.disp == 0:
            # even one row of this vertex bucket busts the dispatch
            # budget: decide on the host instead of crashing a worker
            # — batched through the word-packed numpy closure, chunked
            # in uint32 words so the resident stack is priced like the
            # device path (32× more rows per chunk than bool stacking)
            nw = dense.word_count(n) * dense.WORD_LANES  # word floor
            chunk = _np_chunk_rows(nw)
            for lo in range(0, len(idxs), chunk):
                part = idxs[lo:lo + chunk]
                stack = np.zeros(
                    (len(part), nw, dense.word_count(nw)), np.uint32
                )
                for row, i in enumerate(part):
                    m = np.asarray(mats[i], dtype=bool)
                    plane = np.zeros((nw, nw), bool)
                    plane[: m.shape[0], : m.shape[1]] = m
                    stack[row] = dense.pack_words_np(plane)
                out[part] = _np_packed_has_cycle(stack, nw)
            continue
        batch = np.zeros((len(idxs), n, n), dtype=np.uint8)
        for row, i in enumerate(idxs):
            m = mats[i]
            batch[row, : m.shape[0], : m.shape[1]] = np.asarray(
                m, dtype=bool
            ).astype(np.uint8)
        rows = [(out, i) for i in idxs]
        planned.append(planning.PlannedBucket(n, plan, (batch,), rows))
    if planned:
        _submit_elle_buckets(planned, window, executor)
    return out


def screen_graphs(
    encs: Sequence,
    window: Optional[int] = None,
    executor=None,
    max_dispatch: Optional[int] = None,
) -> List[Optional[ScreenResult]]:
    """Run the full transactional screens for a batch of encoded
    graphs (:class:`jepsen_tpu.elle.encode.EncodedGraph`): bucket by
    (vertex bucket, canonical filter profile), stack each bucket into
    one ``(B, n, n)`` relation batch, and dispatch through the engine
    Executor.  Graphs whose profile exceeds the dispatch budget (cap
    0) come back ``None`` — the caller keeps those on the CPU path."""
    from ..elle import encode as encode_mod
    from ..engine import planning

    results: List[Optional[ScreenResult]] = [None] * len(encs)
    buckets, order = encode_mod.bucket_graphs(encs)
    planned = []
    for key in order:
        n, masks, nonadj = key
        plan = ScreenPlan(n, masks, nonadj, max_dispatch)
        if plan.disp == 0:
            continue  # beyond the budget even one row at a time: CPU
        idxs = buckets[key]
        batch = encode_mod.stack_rel([encs[i] for i in idxs], n)
        rows = [(results, i) for i in idxs]
        planned.append(planning.PlannedBucket(key, plan, (batch,), rows))
    if planned:
        _submit_elle_buckets(planned, window, executor)
    return results


@lru_cache(maxsize=CLOSURE_CACHE_SIZE)
def _reach_fn(n: int):  # jt: allow[budget-missing-cap] — single-matrix (B=1) convenience kernel, see reachability
    @jax.jit
    def close(a):
        r, _ = _bool_closure(a)
        return r

    return close


def reachability(adj: np.ndarray) -> np.ndarray:
    """Full boolean transitive closure of one adjacency matrix (device)."""
    n = _bucket(adj.shape[0])
    padded = np.zeros((n, n), dtype=bool)
    padded[: adj.shape[0], : adj.shape[1]] = adj
    # single-matrix convenience API: the caller wants the closure NOW,
    # there is no batch to overlap with — sanctioned inline sync
    return np.asarray(_reach_fn(n)(jnp.asarray(padded)))[  # jt: allow[trace-sync, budget-direct-dispatch] — B=1, no batch to chunk
        : adj.shape[0], : adj.shape[1]
    ]
