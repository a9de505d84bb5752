"""Dense subset-automaton linearizability kernel for register-family
models — the TPU-first fast path.

The generic WGL kernel (jepsen_tpu.ops.wgl) keeps an explicit frontier of
``(state, linset)`` configs and pays a sort-based dedup/compaction on
every closure step; its capacity F can overflow, degrading to "unknown".
For models whose state enumerates to a small integer domain — read/write
registers, CAS registers, mutexes (the knossos models the reference's
linearizable checker actually runs, jepsen/src/jepsen/checker.clj:19-26)
— there is a representation that maps far better onto a vector machine:

    D[v, s] = 1  iff some linearization order of the ops in subset ``s``
              (of the ≤C currently-open slots) takes the register from
              the promoted prefix to value id ``v``.

``D`` is a *dense* boolean tensor over (value id × linset subset), bit-
packed along the subset axis into uint32 words.  Every WGL operation
becomes a static, branch-free tensor op:

- *value transition*: per event a [C, V, V] one-hot transition matrix is
  built from the candidate op codes (read keeps one value row, write
  folds every row into one, cas moves row a to row b, mutex ops are cas
  in disguise); applying it is a short OR-tree of selects.
- *closure* (linearize open op j): the subset map ``s → s | bit_j`` is,
  on the packed axis, a masked word shift for j < 5 and a static word
  permutation for j ≥ 5 — all C slots advance in ONE vectorized step per
  pass.  No sort, no dedup (the set representation dedups for free), and
  **overflow cannot happen**.
- *completion of slot s* (filter configs that linearized s, promote it):
  the inverse map ``s' → s' \\ bit_s``, a masked shift/permutation again,
  selected among C static variants by the completing slot id.

Per event the closure runs to fixpoint in ≤C passes (a chain linearizes
each open op at most once), so ``lax.while_loop`` capped at C+2 is exact
— there is no truncation/"unknown" path at all.  The whole search is a
``lax.scan`` over events, vmapped over histories, sharded over the
device mesh like the generic kernel.

Cost per event is a handful of fused vector ops on [C, V, 2^C/32]
uint32 tensors — for the practical C ≤ 12, V ≤ 32 envelope a few KB per
history — versus the generic kernel's two O((F + F·C) log) sorts per
closure pass.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .step_kernels import (
    F_READ,
    F_WRITE,
    F_CAS,
    F_READ_ANY,
    F_ACQUIRE,
    F_RELEASE,
    F_ENQUEUE,
    F_DEQUEUE,
    F_RACQUIRE,
    F_RRELEASE,
    F_PACQUIRE,
    F_PRELEASE,
)

#: specs whose state is exactly "current value id" (mutex: 0=free
#: 1=held; owner-mutex: 0=free, else holder's client id — its ops
#: arrive as cas codes from the encoder; reentrant-mutex: 0=free,
#: 2c-1/2c = client c holding once/twice)
DENSE_SPECS = (
    "register", "cas-register", "mutex", "owner-mutex", "reentrant-mutex"
)

#: dense envelope: beyond these the generic frontier kernel takes over
MAX_C = 12   # 2^12 subsets = 128 packed words
MAX_V = 32

#: multi-register composite-state cap: the K-register automaton runs
#: dense over S = Vr^K states (digit per register), e.g. a V^4 map at
#: V ≤ 3, a 2-key map at V ≤ 11 — the small per-key value domains the
#: causal/monotonic-style workloads produce.  Per-event cost scales
#: with S², so past this point the frontier kernel's config-adaptive
#: search wins even though larger S still compiles.
MR_MAX_STATES = 128


def mr_shape_probe(init_state, cand_a, cand_b) -> tuple:
    """(Vr, K) composite shape of an encoded multi-register batch:
    a = per-register value id, b = register index, init packs one
    byte-wide value id per register (step_kernels.py:74-94).  A raw max
    over the PACKED init would wildly overestimate the domain."""
    from .step_kernels import MR_REGISTERS, MR_VALUE_BITS

    init = np.asarray(init_state)
    mask = (1 << MR_VALUE_BITS) - 1
    dig_max = [
        int(((init >> (MR_VALUE_BITS * k)) & mask).max())
        for k in range(MR_REGISTERS)
    ]
    kreg = max(
        int(np.asarray(cand_b).max()) + 1,
        max((k + 1 for k in range(MR_REGISTERS) if dig_max[k] > 0),
            default=1),
    )
    vr = 1 + max(int(np.asarray(cand_a).max()), max(dig_max))
    return vr, kreg


def permits_tables(N: int, P: int):
    """Host-side state enumeration + transition tables for the permit
    (semaphore) automaton: states are multisets of ≤ P client ids
    (1-based, N clients).  Returns (S, acq, rel) with acq/rel of shape
    [N+1, S] mapping (client, state) → state' (or -1 = invalid move:
    acquiring past P total permits, releasing a permit not held)."""
    states = [()]
    if P >= 1:
        states += [(c,) for c in range(1, N + 1)]
    if P >= 2:
        states += [
            (c, d) for c in range(1, N + 1) for d in range(c, N + 1)
        ]
    if P > 2:
        raise ValueError("permit tables support n_permits <= 2")
    index = {st: i for i, st in enumerate(states)}
    S = len(states)
    acq = np.full((N + 1, S), -1, np.int32)
    rel = np.full((N + 1, S), -1, np.int32)
    for i, st in enumerate(states):
        for c in range(1, N + 1):
            if len(st) < P:
                acq[c, i] = index[tuple(sorted(st + (c,)))]
            if c in st:
                out = list(st)
                out.remove(c)
                rel[c, i] = index[tuple(out)]
    return S, acq, rel


def applicable(spec_name: str, C: int, V) -> bool:
    """``V`` is the value-domain size for the register family, or a
    ``(Vr, K)`` pair (per-register domain, register count) for
    multi-register."""
    if spec_name == "unordered-queue":
        # the queue kernel has no V dimension: its state is a pure
        # function of the linset (unique-value ops commute), so only C
        # bounds it — value ids are capped by the encoder at 31 anyway
        return C <= MAX_C
    if spec_name == "multi-register":
        if not isinstance(V, tuple):
            return False
        vr, k = V
        return C <= MAX_C and vr ** k <= MR_MAX_STATES
    if spec_name == "acquired-permits":
        if not isinstance(V, tuple):
            return False
        n_clients, p = V
        if p > 2:
            return False
        S = 1 + n_clients + (
            n_clients * (n_clients + 1) // 2 if p >= 2 else 0
        )
        return C <= MAX_C and S <= MR_MAX_STATES
    return spec_name in DENSE_SPECS and C <= MAX_C and V <= MAX_V


#: _LOMASK[j]: bits of a 32-subset word whose subset index has bit j clear
_LOMASK = (0x55555555, 0x33333333, 0x0F0F0F0F, 0x00FF00FF, 0x0000FFFF)


def _n_words(C: int) -> int:
    return max(1, (1 << C) // 32)


def _subset_maps(C: int):
    """Static per-slot tables for the packed-axis subset maps.

    union (``s → s | bit_j``, image restricted to s ∋ j):
        out[k] = (x[uidx[j,k]] & umask[j,k]) << ushl[j]
    drop (``s → s \\ bit_j``, image restricted to s ∌ j):
        out[k] = (x[didx[j,k]] >> dshr[j]) & dmask[j,k]

    For j < 5 the map moves bits inside a word (mask + shift); for j ≥ 5
    it permutes whole words (static gather + output mask).
    """
    W = _n_words(C)
    k = np.arange(W)
    uidx = np.zeros((C, W), np.int32)
    umask = np.zeros((C, W), np.uint32)
    ushl = np.zeros((C,), np.uint32)
    didx = np.zeros((C, W), np.int32)
    dmask = np.zeros((C, W), np.uint32)
    dshr = np.zeros((C,), np.uint32)
    for j in range(C):
        if j < 5:
            uidx[j] = k
            umask[j] = _LOMASK[j]
            ushl[j] = 1 << j
            didx[j] = k
            dmask[j] = _LOMASK[j]
            dshr[j] = 1 << j
        else:
            wb = 1 << (j - 5)
            uidx[j] = k ^ wb
            umask[j] = np.where((k & wb) != 0, 0xFFFFFFFF, 0)
            didx[j] = k | wb
            dmask[j] = np.where((k & wb) == 0, 0xFFFFFFFF, 0)
    return (
        jnp.asarray(uidx),
        jnp.asarray(umask),
        jnp.asarray(ushl),
        jnp.asarray(didx),
        jnp.asarray(dmask),
        jnp.asarray(dshr),
    )


def _subset_perms(C: int):
    """One-hot word-permutation matrices for the ``union="matmul"``
    lowering: ``Pu[j, w, k] = 1`` iff the union map's word ``k`` reads
    word ``w`` (``w = k ^ wb`` for j ≥ 5, identity below — the j < 5
    maps move bits inside a word, which no matmul over the packed axis
    can do), and ``Pd`` likewise for the drop map's ``k | wb``.  Each
    column holds exactly one 1, so the uint32 matmul is exact: every
    output word is a single product, never a sum that could wrap."""
    W = _n_words(C)
    k = np.arange(W)
    Pu = np.zeros((C, W, W), np.uint32)
    Pd = np.zeros((C, W, W), np.uint32)
    for j in range(C):
        if j < 5:
            Pu[j, k, k] = 1
            Pd[j, k, k] = 1
        else:
            wb = 1 << (j - 5)
            Pu[j, k ^ wb, k] = 1
            Pd[j, k | wb, k] = 1
    return jnp.asarray(Pu), jnp.asarray(Pd)


#: boolean lanes carried per packed adjacency word — the uint32 word
#: width the cycle kernels' ``packed32`` closure and the host
#: ``np.packbits`` fallback both pack to (doc/checker-engines.md
#: "Word-packed closure")
WORD_LANES = 32


def word_count(n: int) -> int:
    """uint32 words needed to carry ``n`` boolean lanes (≥ 1) — the
    ``W`` of the ``(B, n, W)`` packed adjacency layout and the unit
    the word-packed budget math prices rows in
    (:func:`jepsen_tpu.ops.cycles.cycles_max_dispatch`)."""
    return max(1, -(-n // WORD_LANES))


def pack_words_np(bits: np.ndarray) -> np.ndarray:
    """Host word-packing: ``(..., n) bool → (..., W) uint32`` with lane
    ``j`` stored at word ``j // 32``, bit position ``j % 32`` (little
    bit order — the layout ``np.packbits(bitorder="little")`` emits,
    and the one the device-side
    :func:`jepsen_tpu.ops.cycles._pack_words` reproduces bit-for-bit;
    the round-trip property tests pin the two layouts equal)."""
    bits = np.asarray(bits, bool)
    n = bits.shape[-1]
    W = word_count(n)
    pad = W * WORD_LANES - n
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), bool)], axis=-1
        )
    by = np.packbits(bits, axis=-1, bitorder="little").astype(np.uint32)
    by = by.reshape(bits.shape[:-1] + (W, 4))
    return (by[..., 0]
            | (by[..., 1] << np.uint32(8))
            | (by[..., 2] << np.uint32(16))
            | (by[..., 3] << np.uint32(24)))


def unpack_words_np(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of :func:`pack_words_np`: ``(..., W) uint32 → (..., n)``
    bool — lanes past ``n`` are word-floor padding and are dropped."""
    words = np.asarray(words, np.uint32)
    shifts = np.arange(WORD_LANES, dtype=np.uint32)
    lanes = (words[..., None] >> shifts) & np.uint32(1)
    return lanes.reshape(words.shape[:-1] + (-1,))[..., :n].astype(bool)


VALID_UNIONS = ("unroll", "gather", "matmul")


def _check_union(union: str) -> None:
    if union not in VALID_UNIONS:
        raise ValueError(f"unknown dense union lowering {union!r}")


def _xor_permute(x, wb: int):
    """x[..., k] → x[..., k ^ wb] along the last axis, as reshape +
    flip (wb a power of two) — a layout shuffle XLA cannot mistake for
    a data-dependent gather."""
    shape = x.shape
    W = shape[-1]
    xr = x.reshape(*shape[:-1], W // (2 * wb), 2, wb)
    return xr[..., ::-1, :].reshape(*shape)


def _or_select(x, wb: int):
    """x[..., k] → x[..., k | wb]: both halves of each 2·wb block read
    the high half."""
    shape = x.shape
    W = shape[-1]
    xr = x.reshape(*shape[:-1], W // (2 * wb), 2, wb)
    hi = xr[..., 1:2, :]
    return jnp.concatenate([hi, hi], axis=-2).reshape(*shape)


#: subset-map implementation for the dense kernels: "unroll" (default,
#: per-slot static shuffles — reshape/flip for the j≥5 word
#: permutations, pure mask/shift below), "gather" (take_along_axis
#: over constant index tensors), or "matmul" (the j≥5 word
#: permutations as ONE one-hot batched uint32 matmul over the packed
#: axis — _subset_perms — so the union/drop maps ride the same
#: matrix-unit path the closure kernels do).  Same results
#: bit-for-bit (differentially tested).  The default is "unroll"
#: because the gather lowering's index gathers dominate the closure
#: cost, as the roofline model predicts; the gap has not been measured
#: on the chip on today's code.
#: the default subset-union lowering — the ONE definition every
#: consumer (kernel build, the tuner, the benchmark's knob report)
#: reads, so a future default flip can't silently mislabel a run
DEFAULT_UNION = "unroll"


def _union_mode() -> str:
    """Resolved subset-union lowering: ``JEPSEN_TPU_DENSE_UNION`` >
    active calibration (doc/tuning.md — ``jepsen_tpu tune``
    re-measures the unroll/gather/matmul gap per chip) >
    :data:`DEFAULT_UNION`.  The mode is part of the kernel cache key,
    so flipping it can never serve a stale lowering."""
    from ..tune import artifact as _cal

    return _cal.resolve_knob(
        "JEPSEN_TPU_DENSE_UNION",
        lambda v: v.strip() or None,
        lambda cal: cal.union_mode(),
        DEFAULT_UNION,
    )


def _subset_has(C: int):
    """has[j]: [W] uint32 mask of packed bits whose subset index has
    bit j SET — the "configs that linearized slot j" selector."""
    W = _n_words(C)
    k = np.arange(W)
    has = np.zeros((C, W), np.uint32)
    for j in range(C):
        if j < 5:
            has[j] = np.uint32(0xFFFFFFFF ^ _LOMASK[j])
        else:
            has[j] = np.where((k & (1 << (j - 5))) != 0, 0xFFFFFFFF, 0)
    return jnp.asarray(has)


def _or_fold(terms):
    """Tree-OR a static list of equal-shaped uint32 arrays."""
    terms = list(terms)
    while len(terms) > 1:
        terms = [
            terms[i] | terms[i + 1] if i + 1 < len(terms) else terms[i]
            for i in range(0, len(terms), 2)
        ]
    return terms[0]


def build_dense(
    spec_name: str, E: int, C: int, V, mr_shape=None, permits_shape=None,
    union: str = "gather",
):
    """Build the (unjitted) vmapped dense checker for fixed shapes.
    Signature matches wgl.build_batched's result: ``fn(init_state,
    ev_slot, cand_slot, cand_f, cand_a, cand_b) -> (ok, failed_at,
    overflow)`` — with ``overflow`` identically False.

    For ``multi-register`` pass ``mr_shape=(Vr, K)``: the automaton
    then runs over the COMPOSITE state space S = Vr^K (one digit per
    register) with transitions built from the per-register mop codes
    (a = value id, b = register index, step_kernels.py:81-94); V is
    ignored and S takes its place."""
    multi = spec_name == "multi-register"
    reentrant = spec_name == "reentrant-mutex"
    permits = spec_name == "acquired-permits"
    if permits:
        if permits_shape is None:
            raise ValueError("acquired-permits needs permits_shape=(N, P)")
        n_clients, n_permits = permits_shape
        V, acq_np, rel_np = permits_tables(int(n_clients), int(n_permits))
        pm_acq = jnp.asarray(acq_np)  # [N+1, S]
        pm_rel = jnp.asarray(rel_np)
    if multi:
        if mr_shape is None:
            raise ValueError("multi-register needs mr_shape=(Vr, K)")
        vr, kreg = mr_shape
        V = int(vr) ** int(kreg)
        # static digit tables: digit[s, k] of composite state s, and
        # same-except-one-register masks for write transitions
        s_ids = np.arange(V)
        digits_np = np.stack(
            [(s_ids // (vr ** k)) % vr for k in range(kreg)], axis=1
        )  # [S, K]
        same_ex_np = np.zeros((kreg, V, V), bool)  # [K, s', s]
        for k in range(kreg):
            others = np.delete(digits_np, k, axis=1)
            same_ex_np[k] = (others[:, None, :] == others[None, :, :]).all(
                axis=2
            )
        digits_T = jnp.asarray(digits_np.T)  # [K, S]
        same_ex = jnp.asarray(same_ex_np)
        eye_ss = jnp.asarray(np.eye(V, dtype=bool))
        mr_pow = jnp.asarray([vr ** k for k in range(kreg)], jnp.int32)
    elif spec_name not in DENSE_SPECS and not permits:
        raise ValueError(f"no dense kernel for spec {spec_name!r}")
    W = _n_words(C)
    max_closure = C + 2  # ≤C passes reach fixpoint; headroom is free
    uidx, umask, ushl, didx, dmask, dshr = _subset_maps(C)
    uidx_b = jnp.broadcast_to(uidx[:, None, :], (C, V, W))
    didx_b = jnp.broadcast_to(didx[:, None, :], (C, V, W))
    _check_union(union)
    union_unroll = union == "unroll"
    union_matmul = union == "matmul"
    if union_matmul:
        Pu, Pd = _subset_perms(C)

    def dense_check_one(init_state, ev_slot, cand_slot, cand_f, cand_a,
                        cand_b):
        if multi:
            # packed byte-per-register int32 → composite state id
            from .step_kernels import MR_VALUE_BITS

            digs = (
                init_state >> (MR_VALUE_BITS * jnp.arange(kreg))
            ) & ((1 << MR_VALUE_BITS) - 1)
            init_state = jnp.sum(digs.astype(jnp.int32) * mr_pow)
        D0 = jnp.zeros((V, W), jnp.uint32)
        # one config: prefix value = init, empty linset (subset 0, bit 0)
        D0 = lax.dynamic_update_index_in_dim(
            D0, jnp.zeros((W,), jnp.uint32).at[0].set(1), init_state, 0
        )

        def event_body(carry, ev):
            D, done, failed_at, idx = carry
            e_slot, c_slot, c_f, c_a, c_b = ev
            is_pad = e_slot < 0
            c_f = c_f.astype(jnp.int32)
            c_a = c_a.astype(jnp.int32)
            c_b = c_b.astype(jnp.int32)

            # regroup candidate lanes by SLOT id (lanes are sorted by op
            # id, so slot j can sit at any lane; at most one lane holds
            # it) — the packed subset maps need the slot as the index
            eq = c_slot[None, :] == jnp.arange(C, dtype=c_slot.dtype)[:, None]
            active_s = eq.any(axis=1)
            f_s = jnp.sum(jnp.where(eq, c_f[None, :], 0), axis=1)
            a_s = jnp.sum(jnp.where(eq, c_a[None, :], 0), axis=1)
            b_s = jnp.sum(jnp.where(eq, c_b[None, :], 0), axis=1)

            if multi:
                # T[j, s', s] from per-register mop codes: a = value
                # id, b = register index.  write: every digit but reg b
                # unchanged, digit b of s' equals a.  read: s' == s and
                # digit b of s equals a.  read-any: s' == s.
                reg = jnp.clip(b_s, 0, kreg - 1)
                se = jnp.take(same_ex, reg, axis=0)  # [C, S, S]
                d_b = jnp.take(digits_T, reg, axis=0)  # [C, S]
                is_write = f_s == F_WRITE
                is_ra = f_s == F_READ_ANY
                am = a_s[:, None, None]
                T = jnp.where(
                    is_write[:, None, None],
                    se & (d_b[:, :, None] == am),
                    jnp.where(
                        is_ra[:, None, None],
                        eye_ss[None],
                        eye_ss[None] & (d_b[:, None, :] == am),  # read
                    ),
                ) & active_s[:, None, None]
            else:
                # per-slot [C, V, V] transition matrix T[j, v', v]: does
                # linearizing slot j move value v to v'?  (mutex ops are
                # cas in disguise: acquire=cas(0,1), release=cas(1,0))
                is_acq = f_s == F_ACQUIRE
                is_rel = f_s == F_RELEASE
                a_eff = jnp.where(is_acq, 0, jnp.where(is_rel, 1, a_s))
                b_eff = jnp.where(is_acq, 1, jnp.where(is_rel, 0, b_s))
                is_write = f_s == F_WRITE
                is_ra = f_s == F_READ_ANY
                cas_like = (f_s == F_CAS) | is_acq | is_rel
                vp = jnp.arange(V, dtype=jnp.int32)[None, :, None]  # v'
                vv = jnp.arange(V, dtype=jnp.int32)[None, None, :]  # v
                am = a_eff[:, None, None]
                bm = b_eff[:, None, None]
                if permits:
                    # table-driven transitions: tbl[a, s] names the one
                    # target state; -1 (invalid move) can never equal a
                    # state id, so no extra validity mask is needed
                    is_pacq = f_s == F_PACQUIRE
                    a_idx = jnp.clip(a_s, 0, pm_acq.shape[0] - 1)
                    acq_t = jnp.take(pm_acq, a_idx, axis=0)  # [C, S]
                    rel_t = jnp.take(pm_rel, a_idx, axis=0)
                    tbl = jnp.where(is_pacq[:, None], acq_t, rel_t)
                    T = (tbl[:, None, :] == vp) & active_s[:, None, None]
                elif reentrant:
                    # two-pair transitions over state ids {0 free,
                    # 2c-1 once, 2c twice} (a = client id c); a
                    # reentrant batch carries ONLY racq/rrel codes, so
                    # the register nest below never applies — gated at
                    # trace time to keep it out of the flagship path
                    is_racq = f_s == F_RACQUIRE
                    once = (2 * a_s - 1)[:, None, None]
                    twice = (2 * a_s)[:, None, None]
                    racq_T = ((vv == 0) & (vp == once)) | (
                        (vv == once) & (vp == twice)
                    )
                    rrel_T = ((vv == twice) & (vp == once)) | (
                        (vv == once) & (vp == 0)
                    )
                    T = jnp.where(
                        is_racq[:, None, None], racq_T, rrel_T
                    ) & active_s[:, None, None]
                else:
                    T = jnp.where(
                        is_write[:, None, None],
                        vp == am,
                        jnp.where(
                            is_ra[:, None, None],
                            vp == vv,
                            jnp.where(
                                cas_like[:, None, None],
                                (vp == bm) & (vv == am),
                                (vp == am) & (vv == am),  # read
                            ),
                        ),
                    ) & active_s[:, None, None]

            # --- closure: linearize open ops until fixpoint; every slot
            # advances in one vectorized pass ---
            def cond(c):
                _, changed, i = c
                return changed & (i < max_closure)

            def body(c):
                Dc, _, i = c
                # X[j, v', w] = OR_v (T[j, v', v] & Dc[v, w])
                X = _or_fold(
                    jnp.where(T[:, :, v, None], Dc[v][None, None, :], jnp.uint32(0))
                    for v in range(V)
                )
                # subset-union map s → s | bit_j, packed axis
                if union_unroll:
                    add = _or_fold(
                        ((X[j] if j < 5 else _xor_permute(X[j], 1 << (j - 5)))
                         & umask[j][None, :]) << ushl[j]
                        for j in range(C)
                    )
                elif union_matmul:
                    # every slot's word permutation as one batched
                    # one-hot uint32 matmul over the packed axis
                    U = jnp.einsum("jvw,jwk->jvk", X, Pu)
                    U = (U & umask[:, None, :]) << ushl[:, None, None]
                    add = _or_fold(U[j] for j in range(C))
                else:
                    U = jnp.take_along_axis(X, uidx_b, axis=2)
                    U = (U & umask[:, None, :]) << ushl[:, None, None]
                    add = _or_fold(U[j] for j in range(C))
                Dn = Dc | add
                changed = (Dn != Dc).any()
                return (Dn, changed, i + 1)

            Dc, _, _ = lax.while_loop(
                cond, body, (D, jnp.bool_(True), jnp.int32(0))
            )

            # --- completion: keep configs that linearized e_slot, then
            # promote it out of the linset (slot frees for reuse) ---
            if union_unroll:
                Dvar = jnp.stack(
                    [
                        ((Dc if j < 5 else _or_select(Dc, 1 << (j - 5)))
                         >> dshr[j]) & dmask[j][None, :]
                        for j in range(C)
                    ]
                )
            elif union_matmul:
                Ds = jnp.einsum("vw,jwk->jvk", Dc, Pd)
                Dvar = (Ds >> dshr[:, None, None]) & dmask[:, None, :]
            else:
                Ds = jnp.take_along_axis(
                    jnp.broadcast_to(Dc[None], (C, V, W)), didx_b, axis=2
                )
                Dvar = (Ds >> dshr[:, None, None]) & dmask[:, None, :]
            onehot = (e_slot == jnp.arange(C))[:, None, None]
            Df = _or_fold(
                jnp.where(onehot[j], Dvar[j], jnp.uint32(0)) for j in range(C)
            )
            empty = ~(Df != 0).any()

            done2 = done | (~is_pad & empty)
            # dead rows park on an empty frontier: the closure on zeros
            # converges in one pass, so finished histories stop dragging
            # the batch-synchronized while_loop
            D2 = jnp.where(
                done2, jnp.uint32(0), jnp.where(is_pad, D, Df)
            )
            failed_at2 = jnp.where(done | is_pad | ~empty, failed_at, idx)
            return (D2, done2, failed_at2, idx + 1), None

        carry0 = (D0, jnp.bool_(False), jnp.int32(-1), jnp.int32(0))
        (_, done, failed_at, _), _ = lax.scan(
            event_body,
            carry0,
            (ev_slot, cand_slot, cand_f, cand_a, cand_b),
        )
        return ~done, failed_at, jnp.bool_(False)

    return jax.vmap(dense_check_one)


def build_dense_queue(E: int, C: int, union: str = "gather"):
    """Dense unordered-queue kernel: unique-value enqueues/dequeues
    commute, so a config's multiset state is a pure function of its
    linset — the search state collapses to ONE packed bitset over the
    2^C subsets (the register kernel with its value axis removed), plus
    two carried uint32 value-bitsets for the promoted prefix:

        enqC bit v: v was enqueued by a completed op (or initially)
        deqC bit v: v was dequeued by a completed op

    Per candidate the legal-source-subset mask is static algebra:
    enqueues are always legal; a dequeue of v may linearize from
    subsets where v is present — (enq completed, or the open enqueue's
    slot bit is set) and no other open dequeue of v's bit is set and v
    wasn't already dequeued by the prefix.  Closure/completion are the
    same masked-shift subset maps as the register kernel; no sorts,
    no overflow."""
    W = _n_words(C)
    max_closure = C + 2
    uidx, umask, ushl, didx, dmask, dshr = _subset_maps(C)
    _check_union(union)
    union_unroll = union == "unroll"
    union_matmul = union == "matmul"
    if union_matmul:
        Pu, Pd = _subset_perms(C)
    has = _subset_has(C)
    ones = jnp.full((W,), 0xFFFFFFFF, jnp.uint32)
    zeros = jnp.zeros((W,), jnp.uint32)

    def dense_queue_check_one(init_state, ev_slot, cand_slot, cand_f, cand_a,
                              cand_b):
        D0 = jnp.zeros((W,), jnp.uint32).at[0].set(1)  # empty linset
        enqC0 = init_state.astype(jnp.uint32)  # initial contents bitset
        deqC0 = jnp.uint32(0)

        def event_body(carry, ev):
            D, enqC, deqC, done, failed_at, idx = carry
            e_slot, c_slot, c_f, c_a, c_b = ev
            is_pad = e_slot < 0

            # regroup candidate lanes by slot id (cf. register kernel)
            eq = c_slot[None, :] == jnp.arange(C, dtype=c_slot.dtype)[:, None]
            active_s = eq.any(axis=1)
            f_s = jnp.sum(jnp.where(eq, c_f[None, :], 0), axis=1)
            a_s = jnp.sum(jnp.where(eq, c_a[None, :], 0), axis=1)

            is_enq = active_s & (f_s == F_ENQUEUE)
            is_deq = active_s & (f_s == F_DEQUEUE)
            # value ids are 1-based; clamp inactive lanes' shift to 0
            shift = jnp.where(active_s, a_s - 1, 0).astype(jnp.uint32)
            vbit = jnp.where(
                active_s, jnp.uint32(1) << shift, jnp.uint32(0)
            )

            # per-slot-pair value match: does slot k hold the open
            # enqueue (resp. another open dequeue) of slot j's value?
            same_val = a_s[:, None] == a_s[None, :]
            enq_at = same_val & is_enq[None, :] & is_deq[:, None]
            other_deq = (
                same_val & is_deq[None, :] & is_deq[:, None]
                & ~jnp.eye(C, dtype=bool)
            )
            # [C, W] masks via one-hot folds over the static has-table
            e_mask = _or_fold(
                jnp.where(enq_at[:, k, None], has[k][None, :], jnp.uint32(0))
                for k in range(C)
            )
            forbid = _or_fold(
                jnp.where(
                    other_deq[:, k, None], has[k][None, :], jnp.uint32(0)
                )
                for k in range(C)
            )

            enq_done = (enqC & vbit) != 0   # [C] per-slot: v in prefix
            deq_done = (deqC & vbit) != 0
            enq_part = jnp.where(
                enq_done[:, None], ones[None, :], e_mask
            )
            valid = jnp.where(
                is_deq[:, None],
                jnp.where(
                    deq_done[:, None], zeros[None, :], enq_part & ~forbid
                ),
                jnp.where(is_enq[:, None], ones[None, :], zeros[None, :]),
            )

            # --- closure to fixpoint ---
            def cond(c):
                _, changed, i = c
                return changed & (i < max_closure)

            def body(c):
                Dc, _, i = c
                X = Dc[None, :] & valid           # [C, W] legal sources
                if union_unroll:
                    add = _or_fold(
                        ((X[j] if j < 5 else _xor_permute(X[j], 1 << (j - 5)))
                         & umask[j]) << ushl[j]
                        for j in range(C)
                    )
                elif union_matmul:
                    U = jnp.einsum("jw,jwk->jk", X, Pu)
                    U = (U & umask) << ushl[:, None]
                    add = _or_fold(U[j] for j in range(C))
                else:
                    U = jnp.take_along_axis(X, uidx, axis=1)
                    U = (U & umask) << ushl[:, None]
                    add = _or_fold(U[j] for j in range(C))
                Dn = Dc | add
                return (Dn, (Dn != Dc).any(), i + 1)

            Dc, _, _ = lax.while_loop(
                cond, body, (D, jnp.bool_(True), jnp.int32(0))
            )

            # --- completion: filter + promote e_slot ---
            if union_unroll:
                Dvar = jnp.stack(
                    [
                        ((Dc if j < 5 else _or_select(Dc, 1 << (j - 5)))
                         >> dshr[j]) & dmask[j]
                        for j in range(C)
                    ]
                )
            elif union_matmul:
                Ds = jnp.einsum("w,jwk->jk", Dc, Pd)
                Dvar = (Ds >> dshr[:, None]) & dmask
            else:
                Ds = jnp.take_along_axis(
                    jnp.broadcast_to(Dc[None], (C, W)), didx, axis=1
                )
                Dvar = (Ds >> dshr[:, None]) & dmask
            onehot = e_slot == jnp.arange(C)
            Df = _or_fold(
                jnp.where(onehot[j], Dvar[j], jnp.uint32(0)) for j in range(C)
            )
            empty = ~(Df != 0).any()

            # bake the completing op's effect into the prefix bitsets
            comp_enq = (onehot & is_enq).any()
            comp_deq = (onehot & is_deq).any()
            comp_vbit = jnp.sum(jnp.where(onehot, vbit, jnp.uint32(0)))
            enqC2 = jnp.where(~is_pad & comp_enq, enqC | comp_vbit, enqC)
            deqC2 = jnp.where(~is_pad & comp_deq, deqC | comp_vbit, deqC)

            done2 = done | (~is_pad & empty)
            D2 = jnp.where(done2, jnp.uint32(0), jnp.where(is_pad, D, Df))
            failed_at2 = jnp.where(done | is_pad | ~empty, failed_at, idx)
            return (D2, enqC2, deqC2, done2, failed_at2, idx + 1), None

        carry0 = (
            D0, enqC0, deqC0, jnp.bool_(False), jnp.int32(-1), jnp.int32(0)
        )
        (_, _, _, done, failed_at, _), _ = lax.scan(
            event_body,
            carry0,
            (ev_slot, cand_slot, cand_f, cand_a, cand_b),
        )
        return ~done, failed_at, jnp.bool_(False)

    return jax.vmap(dense_queue_check_one)


def make_dense_fn(spec_name: str, E: int, C: int, V):
    """Jitted, cached dense checker (same contract as wgl.make_check_fn).
    The queue kernel has no value axis, so V is normalized out of its
    cache key — otherwise every distinct value-domain (and any initial
    bitset contents, whose numeric max can be huge) would re-jit a
    byte-identical kernel.  For multi-register, V is the (Vr, K)
    composite-shape pair; for acquired-permits the (N, P) client/permit
    pair."""
    if spec_name == "unordered-queue":
        V = 0
    # the union-map mode is part of the cache key: flipping
    # JEPSEN_TPU_DENSE_UNION must rebuild, not hit the old lowering
    union = _union_mode()
    fn = _make_dense_fn_cached(spec_name, E, C, V, union)
    from . import wgl as wgl_mod

    if not hasattr(fn, "safe_dispatch"):
        # dense kernels are overflow-free with no crash-calibrated
        # footprint ceiling (B=16384 runs clean, wgl.py calibration
        # notes), so they carry the full default cap — every dispatch
        # site (check_batch, the pipelined engine) reads ONE
        # ``fn.safe_dispatch`` attribute instead of special-casing
        # engines.  Like the frontier caps this is a PER-CHIP number:
        # on a mesh the engine dispatches n_devices × this many rows
        # per chunk through the fn's shard_map variant
        # (parallel.mesh.shard_fn), each chip holding exactly one cap
        # worth (doc/checker-engines.md "Slice-native dispatch")
        fn.safe_dispatch = wgl_mod.DEFAULT_MAX_DISPATCH
    if wgl_mod.count_kernel_build(fn):
        # engine telemetry: a fresh build means a new (shape, lowering)
        # variant — the jit trace + XLA compile lands on its first
        # dispatch (wgl._timed_run_chunked records it as compile time)
        from .. import obs

        obs.count(
            "jepsen_kernel_builds_total", engine="dense", union=union,
            spec=spec_name,
        )
    return fn


@lru_cache(maxsize=64)
def _make_dense_fn_cached(spec_name: str, E: int, C: int, V, union="gather"):  # jt: allow[budget-missing-cap] — capped by the make_dense_fn wrapper (stamps wgl.DEFAULT_MAX_DISPATCH)  jt: jaxpr(dot_generals<=2*E, dtype=uint32)
    if spec_name == "unordered-queue":
        fn = jax.jit(build_dense_queue(E, C, union=union))
    elif spec_name == "multi-register":
        fn = jax.jit(build_dense(spec_name, E, C, 0, mr_shape=V,
                                 union=union))
    elif spec_name == "acquired-permits":
        fn = jax.jit(build_dense(spec_name, E, C, 0, permits_shape=V,
                                 union=union))
    else:
        fn = jax.jit(build_dense(spec_name, E, C, V, union=union))
    fn.union_mode = union  # rides the mesh shard_fn cache key
    return fn
