"""Host-side encoding of histories into the padded int tensors the WGL
kernel consumes.

The key idea is *slot remapping*: at any moment at most ``slot_cap`` ops
are open (invoked, not yet ok — including indeterminate ops, which stay
open forever), so each op borrows a transient slot id and a config's
linearized-set fits one uint32 **independent of history length**.  Slots
free when their op completes (the completed op joins the common linearized
prefix); info ops hold their slot to the end.

Invoke and info events are no-ops for the search (closure is deferred to
the filtering events — see jepsen_tpu.checker.linear), so the event stream
the device sees is just the *ok* completions, each with a snapshot of the
currently-open candidate ops:

- ``ev_slot[E]``      slot of the op completing at event e (-1 = padding)
- ``cand_slot[E,C]``  open slots at event e (-1 = unused lane)
- ``cand_f/a/b[E,C]`` the op encodings for those slots

Histories whose open-op count ever exceeds slot_cap fall back to the CPU
oracle (reported by returning None), mirroring how the reference degrades
to :unknown rather than guessing (checker.clj:74-85).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import chain, repeat
from array import array
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..history import History, INVOKE, OK, FAIL, INFO
from ..checker import linear
from .. import models as m
from .step_kernels import ModelSpec, is_none, spec_for

DEFAULT_SLOT_CAP = 32

#: history events a columnar chunk (:func:`encode_chunk`) holds at
#: most, whatever the histories' lengths: its fixed numpy cost and its
#: slot pass, one step per event rank, amortize over this many
CHUNK_EVENTS = 1 << 20

#: value ids ride int16 lanes to halve HBM/PCIe traffic for the event
#: stream; histories with more distinct values fall back to the oracle
MAX_VALUE_ID = 32_000


@dataclass
class EncodedHistory:
    init_state: int
    ev_slot: np.ndarray      # [E] int32
    cand_slot: np.ndarray    # [E, C] int8 (-1 = unused lane)
    cand_f: np.ndarray       # [E, C] int8
    cand_a: np.ndarray       # [E, C] int16
    cand_b: np.ndarray       # [E, C] int16
    n_ops: int
    #: peak concurrently-open op count — every slot id used is < this, so
    #: the batch can trim candidate lanes (and linset bits) down to it
    max_open: int = 0


@dataclass
class EncodedBatch:
    """A stack of encoded histories padded to common [B, E, C] shapes."""

    init_state: np.ndarray   # [B] int32
    ev_slot: np.ndarray      # [B, E] int32
    cand_slot: np.ndarray    # [B, E, C]
    cand_f: np.ndarray       # [B, E, C]
    cand_a: np.ndarray       # [B, E, C]
    cand_b: np.ndarray       # [B, E, C]
    #: positions of histories that could not be encoded (oracle fallback)
    fallback: List[int] = field(default_factory=list)
    #: original batch order index per encoded row
    row_history: List[int] = field(default_factory=list)


def _prepare_encoding(history, model, spec):
    """Shared front half: event stream + per-op (f, a, b) codes, or
    None when the model/ops can't be encoded."""
    events, ops = linear.prepare(history, pure_fs=spec.pure_fs)
    valmap: Dict[Any, int] = {}
    try:
        init = spec.init_state(model, valmap)
        enc_ops = [spec.encode_op(op, valmap) for op in ops]
    except ValueError:
        return None
    if len(valmap) > MAX_VALUE_ID:
        return None  # value ids would overflow the int16 lanes
    return events, ops, init, enc_ops


def encode_history(
    history: History,
    model: m.Model,
    slot_cap: int = DEFAULT_SLOT_CAP,
    spec: Optional[ModelSpec] = None,
) -> Optional[EncodedHistory]:
    """Encode one history, or None if unsupported (model has no kernel,
    open-op count exceeds slot_cap, or an op can't be encoded).

    The per-event candidate snapshots are built vectorized — an op is a
    candidate at completion row r iff its invoke precedes r's event
    position and its own completion doesn't, a CONTIGUOUS row range
    computed via searchsorted, so work and memory scale with candidate
    pairs (E × average open ops), never E × n_ops — because host
    encoding is the production ingest path and per-event Python loops
    would cap the device's throughput (SURVEY.md §7, host↔device feed
    rate).  Only slot assignment stays a (cheap, O(n)) sequential
    pass: which slot an op borrows depends on the free set at its
    invoke."""
    import heapq

    spec = spec or spec_for(model)
    if spec is None:
        return None
    pre = _prepare_encoding(history, model, spec)
    if pre is None:
        return None
    events, ops, init, enc_ops = pre

    n = len(ops)
    T = len(events)
    # event-position bookkeeping: t_inv[o], t_done[o] (inf if never ok),
    # and the stream positions of ok events (the kernel's rows)
    t_inv = np.zeros((n,), np.int64)
    t_done = np.full((n,), T + 1, np.int64)
    ok_pos = []
    ok_op_ids = []
    slot = np.full((n,), -1, np.int16)
    free: list = list(range(slot_cap))
    heapq.heapify(free)
    open_count = 0
    max_open = 0
    for t, (kind, op_id) in enumerate(events):
        if kind == "invoke":
            if not free:
                return None  # too many concurrently-open ops
            slot[op_id] = heapq.heappop(free)
            t_inv[op_id] = t
            open_count += 1
            max_open = max(max_open, open_count)
        elif kind == "ok":
            t_done[op_id] = t
            ok_pos.append(t)
            ok_op_ids.append(op_id)
            heapq.heappush(free, int(slot[op_id]))
            open_count -= 1
        # info: op keeps its slot forever

    E = len(ok_pos)
    C = slot_cap
    cand_slot = np.full((E, C), -1, np.int8)
    cand_f = np.zeros((E, C), np.int8)
    cand_a = np.zeros((E, C), np.int16)
    cand_b = np.zeros((E, C), np.int16)
    if E:
        ok_pos_a = np.asarray(ok_pos, np.int64)
        # an op is a candidate at completion row r iff r's event
        # position lies in (t_inv, t_done] — and rows are ordered by
        # position, so each op's candidacy is one CONTIGUOUS row range:
        # total work scales with candidate pairs (E × avg open ops),
        # not E × n_ops
        r_lo = np.searchsorted(ok_pos_a, t_inv, side="right")
        r_hi = np.searchsorted(ok_pos_a, t_done, side="right") - 1
        spans = np.maximum(r_hi - r_lo + 1, 0)
        op_idx = np.repeat(np.arange(n), spans)
        span_starts = np.concatenate(([0], np.cumsum(spans[:-1])))
        within = np.arange(int(spans.sum())) - np.repeat(span_starts, spans)
        rows = np.repeat(r_lo, spans) + within
        # lane order: ops ascending within each row (pairs arrive
        # op-major; resort row-major)
        order = np.lexsort((op_idx, rows))
        rows, op_idx = rows[order], op_idx[order]
        counts = np.bincount(rows, minlength=E)
        row_starts = np.concatenate(([0], np.cumsum(counts[:-1])))
        lanes = np.arange(len(op_idx)) - np.repeat(row_starts, counts)
        fab = np.asarray(enc_ops, np.int32).reshape(n, 3)
        cand_slot[rows, lanes] = slot[op_idx].astype(np.int8)
        cand_f[rows, lanes] = fab[op_idx, 0].astype(np.int8)
        cand_a[rows, lanes] = fab[op_idx, 1].astype(np.int16)
        cand_b[rows, lanes] = fab[op_idx, 2].astype(np.int16)
        ev_slot_arr = slot[np.asarray(ok_op_ids, np.int64)].astype(np.int32)
    else:
        ev_slot_arr = np.full((0,), -1, np.int32)

    return EncodedHistory(
        init_state=init,
        ev_slot=ev_slot_arr,
        cand_slot=cand_slot,
        cand_f=cand_f,
        cand_a=cand_a,
        cand_b=cand_b,
        n_ops=n,
        max_open=max_open,
    )


def _encode_history_loop(
    history: History,
    model: m.Model,
    slot_cap: int = DEFAULT_SLOT_CAP,
    spec: Optional[ModelSpec] = None,
) -> Optional[EncodedHistory]:
    """The straightforward per-event-loop encoder, kept as the
    differential reference for the vectorized encode_history (the two
    must agree array-for-array; tests/test_wgl.py pins it)."""
    spec = spec or spec_for(model)
    if spec is None:
        return None
    pre = _prepare_encoding(history, model, spec)
    if pre is None:
        return None
    events, ops, init, enc_ops = pre

    E = sum(1 for kind, _ in events if kind == "ok")
    C = slot_cap
    ev_slot_arr = np.full((E,), -1, np.int32)
    cand_slot = np.full((E, C), -1, np.int8)
    cand_f = np.zeros((E, C), np.int8)
    cand_a = np.zeros((E, C), np.int16)
    cand_b = np.zeros((E, C), np.int16)

    slot_of: Dict[int, int] = {}
    free = sorted(range(slot_cap), reverse=True)  # pop() takes smallest
    row = 0
    max_open = 0
    for kind, op_id in events:
        if kind == "invoke":
            if not free:
                return None  # too many concurrently-open ops
            slot_of[op_id] = free.pop()
            max_open = max(max_open, len(slot_of))
        elif kind == "ok":
            # snapshot of open ops (incl. the completing one) BEFORE filter
            for lane, oid in enumerate(sorted(slot_of.keys())):
                f, a, b = enc_ops[oid]
                cand_slot[row, lane] = slot_of[oid]
                cand_f[row, lane] = f
                cand_a[row, lane] = a
                cand_b[row, lane] = b
            ev_slot_arr[row] = slot_of[op_id]
            row += 1
            free.append(slot_of.pop(op_id))
            free.sort(reverse=True)
        # info: op keeps its slot forever

    return EncodedHistory(
        init_state=init,
        ev_slot=ev_slot_arr,
        cand_slot=cand_slot,
        cand_f=cand_f,
        cand_a=cand_a,
        cand_b=cand_b,
        n_ops=len(ops),
        max_open=max_open,
    )


_T_INVOKE, _T_OK, _T_FAIL, _T_INFO = 0, 1, 2, 3
#: free-slot masks are uint64, and a full mask must not wrap
_MAX_COLUMNAR_SLOTS = 63
#: one numpy step of the slot pass costs about as much as walking this
#: many events one at a time in Python; a chunk with fewer events than
#: this a rank (a few long histories) walks them
_RANK_STEP_EVENTS = 48
#: the columnar pass's fixed cost, and the fixed cost of one history's
#: encode_history, each in events of the per-event time the pass saves
#: (about 500 us, 170 us and 0.5 us on a CPU host); a chunk that cannot
#: pay for the pass (one or two short histories) takes encode_history
_PASS_COST_EVENTS, _HISTORY_COST_EVENTS = 1000, 340


def encode_chunk(
    histories: Sequence[History],
    models: Sequence[m.Model],
    slot_cap: int = DEFAULT_SLOT_CAP,
    spec: Optional[ModelSpec] = None,
) -> Tuple[List[Optional[EncodedHistory]], List[int]]:
    """Encode many histories at once: ``(encoded, per_history)``, where
    ``encoded[i]`` equals ``encode_history(histories[i], models[i],
    slot_cap, spec)`` array for array and ``per_history`` lists the
    positions that took that per-history path.

    For a spec with a columnar op encoder (``spec.encode_columns``) the
    only per-event Python left is reading the ops' fields, one pass a
    field; pairing, dropping, value ids, slot assignment and the
    candidate snapshot run as numpy over the chunk's concatenated
    event columns (:func:`_encode_columns`).  Other specs, a chunk too
    small to pay for that pass, and any history it cannot decide (a
    process invoking twice without completing, a completion with no
    open invoke, an op ``encode_op`` would refuse or a value the
    columnar encoder does not read) take :func:`encode_history`."""
    n = len(histories)
    cols = spec.encode_columns if spec is not None else None
    out: List[Optional[EncodedHistory]] = [None] * n
    redo: List[int] = list(range(n))
    if (cols is not None and slot_cap <= _MAX_COLUMNAR_SLOTS
            and sum(map(len, histories)) + _HISTORY_COST_EVENTS * n
            >= _PASS_COST_EVENTS):
        try:
            out, redo = _encode_columns(histories, models, slot_cap, spec,
                                        cols)
        except _Unreadable:
            pass
    for i in redo:
        out[i] = encode_history(histories[i], models[i], slot_cap, spec)
    return out, redo


def _lex_order(major: np.ndarray, n_major: int, minor: np.ndarray,
               n_minor: int) -> np.ndarray:
    """Stable order by (major, minor), both non-negative and below
    their bounds: one or two 16-bit radix passes where they fit."""
    if n_major * n_minor <= 1 << 16:
        return np.argsort((major * n_minor + minor).astype(np.uint16),
                          kind="stable")
    if n_major <= 1 << 16 and n_minor <= 1 << 16:
        o = np.argsort(minor.astype(np.uint16), kind="stable")
        return o[np.argsort(major[o].astype(np.uint16), kind="stable")]
    return np.lexsort((minor, major))


def _group_rank(sorted_key: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """For a sorted key: (first-of-group flags, index within group)."""
    n = sorted_key.shape[0]
    first = np.ones((n,), bool)
    first[1:] = sorted_key[1:] != sorted_key[:-1]
    idx = np.arange(n)
    return first, idx - np.maximum.accumulate(np.where(first, idx, 0))


def _dense(x: np.ndarray) -> Tuple[np.ndarray, int]:
    """``x``'s values renumbered 0.. in ascending order, and how many."""
    if not x.size:
        return x, 0
    lo = int(x.min())
    span = int(x.max()) - lo + 1
    if span > x.shape[0] + (1 << 16):
        u, d = np.unique(x, return_inverse=True)
        return d.reshape(-1), u.shape[0]
    seen = np.zeros((span,), bool)
    seen[x - lo] = True
    return (np.cumsum(seen) - 1)[x - lo], int(seen.sum())


class _Codes(dict):
    """Codes from 1 by key; any other key codes 0."""

    def __missing__(self, key):
        return 0


_TYPE_CODES = _Codes({INVOKE: 1 + _T_INVOKE, OK: 1 + _T_OK,
                      FAIL: 1 + _T_FAIL, INFO: 1 + _T_INFO})


class _Unreadable(Exception):
    """A field the columnar pass cannot read: an unhashable type, f or
    value, or a process past int64.  The chunk takes
    :func:`encode_history`, which meets it as it always has."""


def _read_fields(ops: list, fs: Sequence[Any]):
    """The per-event Python of a chunk, no Op copied: ``(type codes, f
    codes, which events linear.prepare reads, processes, values,
    which values are None)``.  A type or f outside the known ones codes
    -1; linear.prepare passes over an event of another type or of a
    process that is no int (a nemesis's), so it is not read."""
    N = len(ops)
    tc = _TYPE_CODES
    fc = _Codes({f: i + 1 for i, f in enumerate(fs)})

    def code(tc, fc):
        # a byte an event: bits 0-2 the type's code, 3-6 the f's, 7 a
        # None value
        return np.frombuffer(bytes([
            (o.value is None) << 7 | fc[o.f] << 3 | tc[o.type]
            for o in ops]), np.uint8)

    try:
        try:
            # plain dicts look up faster; they raise on another key
            word = code(dict(tc), dict(fc))
        except KeyError:  # another type or f (a nemesis's): codes 0
            word = code(tc, fc)
    except TypeError as e:  # an unhashable type or f
        raise _Unreadable from e
    tcode = (word & 7).astype(np.int8) - 1
    fcode = ((word >> 3) & 15).astype(np.int8) - 1
    procs = [o.process for o in ops]
    try:
        # a sum of ints is an int: every process is one (the usual
        # case, and the cheap test of it)
        ints = type(sum(procs)) is int
    except TypeError:
        ints = False
    live = tcode >= 0
    if not ints:
        live &= np.fromiter(map(isinstance, procs, repeat(int)), bool, N)
        procs = [p if ok else 0 for p, ok in zip(procs, live.tolist())]
    try:
        proc = np.frombuffer(array("q", procs), np.int64)
    except OverflowError as e:
        raise _Unreadable from e
    return (tcode, fcode, live, proc,
            np.fromiter([o.value for o in ops], object, N), word >= 128)


def _pair(hid: np.ndarray, n: int, tcode: np.ndarray, live: np.ndarray,
          proc: np.ndarray):
    """linear.prepare's pairing, by a stable sort on (history,
    process): ``(invokes, completions, bad)`` with each invoke's
    position (ascending), its completion's (-1 if none), and the
    histories where some process does not alternate invoke,
    completion, invoke, ... (they take the per-history path)."""
    ev = np.flatnonzero(live)
    pd, n_procs = _dense(proc[ev])
    order = _lex_order(hid[ev], n, pd, n_procs)
    se = ev[order]
    first, k = _group_rank(hid[se] * n_procs + pd[order])
    even = (k & 1) == 0
    bad = np.zeros((n,), bool)
    bad[hid[se[(tcode[se] == _T_INVOKE) != even]]] = True
    paired = np.flatnonzero(even[:-1] & ~first[1:])
    comp_of = np.full((tcode.shape[0],), -1, np.int64)
    comp_of[se[paired]] = se[paired + 1]
    inv = np.flatnonzero(live & (tcode == _T_INVOKE))
    return inv, comp_of[inv], bad


def _value_ids(n: int, hs: np.ndarray, init_val: np.ndarray,
               oh: np.ndarray, a_val: np.ndarray, b_val: np.ndarray):
    """``_value_id``'s ids for a chunk's values: each history numbers
    its distinct values from 1 in order of first occurrence over its
    init value (histories ``hs``), then each op's a and b (op history
    ``oh``); None is V_UNKNOWN, 0.  Returns (init ids by history, a
    ids, b ids, distinct values by history)."""
    K = oh.shape[0]
    nh = hs.shape[0]
    # the tokens in order: inits, then each op's a and, where it has
    # one, its b
    has_b = ~is_none(b_val.tolist())
    at_a = nh + np.arange(K) + np.cumsum(has_b) - has_b
    at_b = (at_a + 1)[has_b]
    tok = np.empty((nh + K + at_b.shape[0],), object)
    tok[:nh] = init_val
    tok[at_a] = a_val
    tok[at_b] = b_val[has_b]
    tok_h = np.empty((tok.shape[0],), np.int64)
    tok_h[:nh] = hs
    tok_h[at_a] = oh
    tok_h[at_b] = oh[has_b]
    toks = tok.tolist()
    try:
        gid = {v: i for i, v in enumerate(dict.fromkeys(toks))}
    except TypeError as e:  # an unhashable value
        raise _Unreadable from e
    G = len(gid)
    g = np.fromiter(map(gid.__getitem__, toks), np.int64, len(toks))
    vid = np.zeros((len(toks),), np.int64)
    valued = np.flatnonzero(g != gid.get(None, -1))
    n_values = np.zeros((n,), np.int64)
    if valued.size:
        # a history's tokens lie in its own order, so the first of
        # each (history, value) group after a stable sort is that
        # value's first occurrence
        vh = tok_h[valued]
        order = _lex_order(vh, n, g[valued], G)
        starts = np.flatnonzero(
            _group_rank(vh[order] * G + g[valued][order])[0])
        at = valued[order[starts]]
        uh = tok_h[at]
        # number each history's values by where they first occur
        by_pos = np.lexsort((at, uh))
        uvid = np.empty((starts.shape[0],), np.int64)
        uvid[by_pos] = _group_rank(uh[by_pos])[1] + 1
        n_values = np.bincount(uh, minlength=n)
        vid[valued[order]] = np.repeat(
            uvid, np.diff(np.append(starts, order.shape[0])))
    init_id = np.zeros((n,), np.int64)
    init_id[hs] = vid[:nh]
    b_id = np.zeros((K,), np.int64)
    b_id[has_b] = vid[at_b]
    return init_id, vid[at_a], b_id, n_values


def _assign_slots(n: int, N: int, hid: np.ndarray, inv: np.ndarray,
                  ok_at: np.ndarray, ok_op: np.ndarray) -> np.ndarray:
    """Each op's slot, the smallest free one at its invoke as
    encode_history's heap hands them out; a completion frees its op's
    slot.  Sequential only over event rank: each numpy step takes the
    rank's event of every history of the chunk at once, its free slots
    a ``uint64`` bit mask.  A chunk of few histories walks its events
    one at a time instead, with the same masks as Python ints."""
    K = inv.shape[0]
    kind = np.zeros((N,), np.int8)  # 1 invoke, 2 ok completion
    kind[inv] = 1
    kind[ok_at] = 2
    ev_op = np.zeros((N,), np.int64)
    ev_op[inv] = np.arange(K)
    ev_op[ok_at] = ok_op
    evs = np.flatnonzero(kind)
    ev_h = hid[evs]
    rank = np.arange(evs.shape[0]) - np.searchsorted(ev_h, np.arange(n))[ev_h]
    R = int(rank.max()) + 1 if rank.size else 0
    one = np.uint64(1)
    if evs.shape[0] < _RANK_STEP_EVENTS * R:
        # few histories a rank: walk the events one at a time instead
        mask = [0] * n
        bits = [0] * K
        for h, op, k in zip(ev_h.tolist(), ev_op[evs].tolist(),
                            kind[evs].tolist()):
            if k == 1:
                mk = mask[h]
                b = ~mk & (mk + 1)  # the lowest clear bit
                mask[h] = mk | b
                bits[op] = b
            else:
                mask[h] ^= bits[op]
        return np.bitwise_count(np.array(bits, np.uint64) - one).astype(
            np.int8)
    step = 2 * rank + kind[evs] - 1  # each rank's invokes, then its oks
    order = _lex_order(step, 2 * R, np.zeros_like(step), 1)
    bounds = np.zeros((2 * R + 1,), np.int64)
    np.cumsum(np.bincount(step, minlength=2 * R), out=bounds[1:])
    bounds = bounds.tolist()
    s_h = ev_h[order]
    s_op = ev_op[evs[order]]
    mask = np.zeros((n,), np.uint64)
    bit = np.zeros((K,), np.uint64)
    for r in range(R):
        lo, mid, hi = bounds[2 * r], bounds[2 * r + 1], bounds[2 * r + 2]
        if lo < mid:
            h = s_h[lo:mid]
            mk = mask[h]
            b = ~mk & (mk + one)  # the lowest clear bit
            mask[h] = mk | b
            bit[s_op[lo:mid]] = b
        if mid < hi:
            h = s_h[mid:hi]
            mask[h] &= ~bit[s_op[mid:hi]]
    return np.bitwise_count(bit - one).astype(np.int8)


def _encode_columns(histories, models, slot_cap, spec, cols):
    """The columnar pass of :func:`encode_chunk`: ``(encoded,
    per_history)`` with ``encoded[i]`` None for a history that rides
    the oracle and for one left to the per-history path."""
    n = len(histories)
    out: List[Optional[EncodedHistory]] = [None] * n
    lens = np.fromiter(map(len, histories), np.int64, n)
    hstart = np.zeros((n + 1,), np.int64)
    np.cumsum(lens, out=hstart[1:])
    ops = list(chain.from_iterable(histories))
    N = len(ops)
    hid = np.repeat(np.arange(n), lens)
    tcode, fcode, live, proc, values, vnone = _read_fields(ops, cols.fs)
    del ops

    # -- ops: pair, then drop what linear.prepare drops -------------------
    inv, comp, bad = _pair(hid, n, tcode, live, proc)
    ct = np.where(comp >= 0, tcode[comp], -1)
    # a failed op never happened, nor a crashed pure one (prepare reads
    # the f of its completion)
    drop = (ct == _T_FAIL) | ((ct == _T_INFO) & np.isin(
        fcode[comp], [cols.fs.index(f) for f in spec.pure_fs]))
    keep = ~drop & ~bad[hid[inv]]
    inv, comp, ct = inv[keep], comp[keep], ct[keep]
    oh = hid[inv]
    # a completion's non-None value is the op's value
    src = np.where((comp >= 0) & ~vnone[comp], comp, inv)
    fk, a_val, b_val, undecided = cols.encode(fcode[inv], values[src],
                                              vnone[src])
    bad[oh[undecided]] = True

    # -- init states: the spec's own, once per distinct model ------------
    init_val = np.full((n,), None, object)
    seen: Dict[int, Any] = {}
    for i in np.flatnonzero(~bad).tolist():
        mdl = models[i]
        vm = seen.get(id(mdl))
        if vm is None:
            vm = {}
            try:
                spec.init_state(mdl, vm)
            except ValueError:
                vm = False  # encode_history decides such a history
            seen[id(mdl)] = vm
        if vm is False or len(vm) > 1:
            bad[i] = True
        elif vm:
            init_val[i] = next(iter(vm))
    ko = ~bad[oh]
    inv, comp, ct, oh = inv[ko], comp[ko], ct[ko], oh[ko]
    fk, a_val, b_val = fk[ko], a_val[ko], b_val[ko]
    hs = np.flatnonzero(~bad)
    init_id, a_id, b_id, n_values = _value_ids(n, hs, init_val[hs], oh,
                                               a_val, b_val)

    # -- past MAX_VALUE_ID or slot_cap, a history rides the oracle -------
    is_ok = ct == _T_OK
    delta = np.zeros((N,), np.int64)
    delta[inv] = 1
    delta[comp[is_ok]] = -1
    opened = np.cumsum(delta)
    max_open = np.zeros((n,), np.int64)
    nonempty = np.flatnonzero(lens)
    if nonempty.size:
        before = np.concatenate(([0], opened))[hstart[nonempty]]
        max_open[nonempty] = (
            np.maximum.reduceat(opened, hstart[nonempty]) - before)
    over = (n_values > MAX_VALUE_ID) | (max_open > slot_cap)
    ko = ~over[oh]
    inv, comp, oh, fk = inv[ko], comp[ko], oh[ko], fk[ko]
    a_id, b_id, is_ok = a_id[ko], b_id[ko], is_ok[ko]
    K = inv.shape[0]
    ok_at = comp[is_ok]
    ok_op = np.flatnonzero(is_ok)
    slot = _assign_slots(n, N, hid, inv, ok_at, ok_op)

    # -- candidate snapshot, as encode_history's, on chunk positions -----
    # rows are the ok completions; oks[t] = rows before position t
    oks = np.zeros((N + 1,), np.int64)
    oks[ok_at + 1] = 1
    np.cumsum(oks, out=oks)
    E = int(oks[-1])
    C = slot_cap
    cand_slot = np.full((E, C), -1, np.int8)
    cand_f = np.zeros((E, C), np.int8)
    cand_a = np.zeros((E, C), np.int16)
    cand_b = np.zeros((E, C), np.int16)
    ev_slot = np.full((E,), -1, np.int32)
    row_first = oks[hstart[:-1]]
    if E:
        # an op is a candidate from the first row after its invoke
        # through its own completion, or its history's last row
        r_lo = oks[inv + 1]
        r_hi = oks[np.where(is_ok, comp, hstart[oh + 1] - 1) + 1] - 1
        spans = np.maximum(r_hi - r_lo + 1, 0)
        P = int(spans.sum())
        op_idx = np.repeat(np.arange(K), spans)
        span_starts = np.cumsum(spans) - spans
        rows = np.repeat(r_lo - span_starts, spans) + np.arange(P)
        # lanes hold a row's candidates by op: an op's lane is how many
        # lower ops are open at the row.  At its first row that is
        # every lower op of its history but the one completed at each
        # earlier row; then one less at each row where a lower op
        # completes (one op completes at each row)
        row_op = np.empty((E,), np.int64)
        row_op[oks[ok_at]] = ok_op
        lower = (row_op[rows] < op_idx).astype(np.int64)
        seen_lower = np.cumsum(lower) - lower
        lane0 = (np.arange(K) - np.searchsorted(oh, oh)) - (r_lo - row_first[oh])
        lane0 += seen_lower[np.minimum(span_starts, max(P - 1, 0))]
        flat = rows * C + (np.repeat(lane0, spans) - seen_lower)
        cand_slot.reshape(-1)[flat] = slot[op_idx]
        cand_f.reshape(-1)[flat] = fk[op_idx]
        cand_a.reshape(-1)[flat] = a_id[op_idx]
        cand_b.reshape(-1)[flat] = b_id[op_idx]
        ev_slot[:] = slot[row_op]
    done = ~bad & ~over
    n_ops = np.bincount(oh, minlength=n)
    for i, lo, hi, init, nop, mo in zip(
        np.flatnonzero(done).tolist(), row_first[done].tolist(),
        oks[hstart[1:]][done].tolist(), init_id[done].tolist(),
        n_ops[done].tolist(), max_open[done].tolist(),
    ):
        out[i] = EncodedHistory(
            init_state=init, ev_slot=ev_slot[lo:hi],
            cand_slot=cand_slot[lo:hi], cand_f=cand_f[lo:hi],
            cand_a=cand_a[lo:hi], cand_b=cand_b[lo:hi],
            n_ops=nop, max_open=mo,
        )
    return out, np.flatnonzero(bad).tolist()


def round_up(n: int, multiple: int = 64) -> int:
    """Bucket sizes to multiples to bound recompilation."""
    if n <= 0:
        return multiple
    return ((n + multiple - 1) // multiple) * multiple


def bucket_key(
    e: EncodedHistory, slot_cap: int, event_bucket: int = 64
) -> tuple:
    """The padded ``(E, C)`` shape bucket one encoded history stacks
    into: events round to ``event_bucket`` (bounding recompiles),
    candidate lanes to the history's own peak concurrency rounded to 4
    and capped at ``slot_cap``.  Shared by :func:`batch_encode`'s
    bucketed mode and the engine's streaming bucketer
    (:mod:`jepsen_tpu.engine.planning`), so the two can never disagree
    about which histories share a compiled shape."""
    E = round_up(e.ev_slot.shape[0], event_bucket)
    C = min(slot_cap, round_up(e.max_open, 4))
    return E, C


def global_shape(
    encoded: Sequence[EncodedHistory], slot_cap: int, event_bucket: int = 64
) -> tuple:
    """The single-batch padded ``(E, C)`` of :func:`batch_encode`'s
    unbucketed mode: every history padded to the global max event
    count, candidate lanes to the batch's peak concurrency (rounded to
    4, capped at ``slot_cap``) — this shrinks the frontier-expansion
    width and sort size, usually the dominant cost.  The engine never
    uses it: it always stacks per :func:`bucket_key`."""
    E = round_up(max(e.ev_slot.shape[0] for e in encoded), event_bucket)
    C = min(slot_cap, round_up(max(e.max_open for e in encoded), 4))
    return E, C


def empty_batch(slot_cap: int, fallback=(), rows=()) -> EncodedBatch:
    """A zero-row EncodedBatch (the all-fallback shape)."""
    return EncodedBatch(
        init_state=np.zeros((0,), np.int32),
        ev_slot=np.zeros((0, 0), np.int32),
        cand_slot=np.zeros((0, 0, slot_cap), np.int8),
        cand_f=np.zeros((0, 0, slot_cap), np.int8),
        cand_a=np.zeros((0, 0, slot_cap), np.int16),
        cand_b=np.zeros((0, 0, slot_cap), np.int16),
        fallback=list(fallback),
        row_history=list(rows),
    )


def stack_encoded(
    encoded: Sequence[EncodedHistory],
    rows: Sequence[int],
    E: int,
    C: int,
    fallback=(),
) -> EncodedBatch:
    """Stack encoded histories into one padded ``[B, E, C]`` batch.
    Candidate lanes are trimmed to ``C`` — sound because every slot id
    used is < the history's ``max_open`` ≤ C (the caller derives C from
    the stack's peak concurrency, see :func:`bucket_key`)."""
    B = len(encoded)
    init_state = np.zeros((B,), np.int32)
    ev_slot = np.full((B, E), -1, np.int32)
    cand_slot = np.full((B, E, C), -1, np.int8)
    cand_f = np.zeros((B, E, C), np.int8)
    cand_a = np.zeros((B, E, C), np.int16)
    cand_b = np.zeros((B, E, C), np.int16)
    for bi, e in enumerate(encoded):
        n = e.ev_slot.shape[0]
        init_state[bi] = e.init_state
        ev_slot[bi, :n] = e.ev_slot
        cand_slot[bi, :n] = e.cand_slot[:, :C]
        cand_f[bi, :n] = e.cand_f[:, :C]
        cand_a[bi, :n] = e.cand_a[:, :C]
        cand_b[bi, :n] = e.cand_b[:, :C]
    return EncodedBatch(
        init_state=init_state,
        ev_slot=ev_slot,
        cand_slot=cand_slot,
        cand_f=cand_f,
        cand_a=cand_a,
        cand_b=cand_b,
        fallback=list(fallback),
        row_history=list(rows),
    )


def batch_encode(
    histories: Sequence[History],
    model: m.Model,
    slot_cap: int = DEFAULT_SLOT_CAP,
    event_bucket: int = 64,
    bucketed: bool = False,
):
    """Encode histories into padded batches; unencodable ones land in
    ``fallback`` for the CPU oracle.

    ``bucketed=False`` (the default) returns ONE :class:`EncodedBatch`
    padded to the global max event count (:func:`global_shape`) —
    every short history pays the longest history's padding; the
    kernel-level callers that drive one batch by hand use this form.
    ``bucketed=True`` instead returns a ``List[EncodedBatch]``, one per
    padded ``(E, C)`` shape bucket (:func:`bucket_key`), sorted by
    shape — the shapes the engine dispatches; the global
    ``fallback`` list rides on the FIRST returned batch (an
    all-fallback input returns a single zero-row batch carrying it)."""
    spec = spec_for(model)
    encoded: List[EncodedHistory] = []
    rows: List[int] = []
    fallback: List[int] = []
    for i, h in enumerate(histories):
        e = encode_history(h, model, slot_cap, spec) if spec else None
        if e is None:
            fallback.append(i)
        else:
            encoded.append(e)
            rows.append(i)

    if not bucketed:
        if not encoded:
            return empty_batch(slot_cap, fallback, rows)
        E, C = global_shape(encoded, slot_cap, event_bucket)
        return stack_encoded(encoded, rows, E, C, fallback)

    buckets: dict = {}
    for e, i in zip(encoded, rows):
        buckets.setdefault(bucket_key(e, slot_cap, event_bucket), []).append(
            (e, i)
        )
    if not buckets:
        return [empty_batch(slot_cap, fallback, rows)]
    out: List[EncodedBatch] = []
    for key in sorted(buckets):
        E, C = key
        es = [e for e, _ in buckets[key]]
        idxs = [i for _, i in buckets[key]]
        out.append(
            stack_encoded(es, idxs, E, C, fallback if not out else ())
        )
    return out
