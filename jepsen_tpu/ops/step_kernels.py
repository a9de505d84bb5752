"""Branchless model step kernels for the TPU linearizability search.

Each supported model (see jepsen_tpu.models for the CPU oracles these are
differentially tested against) gets:

- an integer encoding of its state (one int32),
- an op encoding ``(f, a, b)`` of int32s, and
- a pure, branchless ``step(state, f, a, b) -> (state', ok)`` built from
  jnp.where/select so it vectorizes over (frontier × candidate) lanes and
  compiles into the surrounding scan without data-dependent control flow.

Covers the knossos.model set the reference's linearizable checker uses
(jepsen/src/jepsen/checker.clj:19-26,185-216): register, cas-register,
mutex, multi-register, and unordered-queue (as a unique-element bitset —
see unordered_queue_step for the envelope).  FIFO queues stay on the CPU
oracle: their state is the pending *sequence*, which depends on the
linearization order itself and admits no fixed-width encoding.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from itertools import repeat
from operator import is_, itemgetter
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import models as m

# Op function codes shared by the register-family kernels.
F_READ = 0        # a = expected value id (observed at completion)
F_WRITE = 1       # a = written value id
F_CAS = 2         # a = expected old value id, b = new value id
F_READ_ANY = 3    # read with unknown value: always ok, no state change
F_ACQUIRE = 4     # mutex
F_RELEASE = 5     # mutex
F_ENQUEUE = 6     # unordered queue: a = value id
F_DEQUEUE = 7     # unordered queue: a = observed value id
F_RACQUIRE = 8    # reentrant mutex: a = client id (see reentrant_mutex_step)
F_RRELEASE = 9    # reentrant mutex: a = client id
F_PACQUIRE = 10   # permit (semaphore) acquire: a = client id
F_PRELEASE = 11   # permit release: a = client id

#: Value id reserved for "unknown/None". Known values are 1-based.
V_UNKNOWN = 0


def register_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Read/write register.  (oracle: models.Register)"""
    is_read = f == F_READ
    is_write = f == F_WRITE
    is_read_any = f == F_READ_ANY
    ok = is_write | is_read_any | (is_read & (state == a))
    state2 = jnp.where(is_write, a, state)
    return state2, ok


def cas_register_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Read/write/compare-and-set register.  (oracle: models.CASRegister)"""
    is_read = f == F_READ
    is_write = f == F_WRITE
    is_cas = f == F_CAS
    is_read_any = f == F_READ_ANY
    cas_ok = is_cas & (state == a)
    ok = is_write | is_read_any | (is_read & (state == a)) | cas_ok
    state2 = jnp.where(is_write, a, jnp.where(cas_ok, b, state))
    return state2, ok


def mutex_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Lock: state 0 = free, 1 = held.  (oracle: models.Mutex)"""
    is_acq = f == F_ACQUIRE
    is_rel = f == F_RELEASE
    ok = (is_acq & (state == 0)) | (is_rel & (state == 1))
    state2 = jnp.where(is_acq, 1, jnp.where(is_rel, 0, state)).astype(state.dtype)
    return state2, ok


def reentrant_mutex_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Reentrant owner-aware mutex with hold bound 2 (the hazelcast CP
    probe's reentrant-lock-acquire-count).  State ids: 0 = free,
    2c-1 = client c holds once, 2c = client c holds twice (a = client
    id c ≥ 1).  acquire: free → (c,1) or (c,1) → (c,2); release:
    (c,2) → (c,1) or (c,1) → free.  (oracle: models.ReentrantMutex)"""
    is_acq = f == F_RACQUIRE
    is_rel = f == F_RRELEASE
    once = 2 * a - 1
    twice = 2 * a
    acq_fresh = is_acq & (state == 0)
    acq_re = is_acq & (state == once)
    rel_two = is_rel & (state == twice)
    rel_one = is_rel & (state == once)
    ok = acq_fresh | acq_re | rel_two | rel_one
    state2 = jnp.where(
        acq_fresh, once,
        jnp.where(
            acq_re, twice,
            jnp.where(rel_two, once, jnp.where(rel_one, 0, state)),
        ),
    ).astype(state.dtype)
    return state2, ok


#: multi-register packing: up to 4 registers, 8-bit value ids each, in
#: one int32 state word.  Wider maps fall back to the CPU oracle.
MR_REGISTERS = 4
MR_VALUE_BITS = 8
MR_MAX_VALUE_ID = (1 << MR_VALUE_BITS) - 1


def multi_register_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Single-mop multi-register: b = register index, a = value id; the
    int32 state packs MR_REGISTERS byte-wide registers.
    (oracle: models.MultiRegister)"""
    sh = (b.astype(jnp.int32) & (MR_REGISTERS - 1)) * MR_VALUE_BITS
    mask = jnp.int32(MR_MAX_VALUE_ID) << sh
    cur = (state >> sh) & MR_MAX_VALUE_ID
    is_read = f == F_READ
    is_write = f == F_WRITE
    is_read_any = f == F_READ_ANY
    ok = is_write | is_read_any | (is_read & (cur == a))
    written = (state & ~mask) | ((a.astype(jnp.int32) & MR_MAX_VALUE_ID) << sh)
    state2 = jnp.where(is_write, written, state)
    return state2, ok


#: unordered-queue packing: a bitset of present values in one int32 —
#: sound only when every value appears at most once (initial contents +
#: enqueues), which IS the shape real queue workloads generate (unique
#: elements, e.g. suites/common.py queue_workload); histories breaking
#: it fall back to the oracle at encode time.  A FIFO queue's state is
#: the *sequence* of pending values — it depends on the linearization
#: order itself, so no fixed-width encoding exists without bounding the
#: whole history; FIFOQueue therefore stays on the CPU oracle
#: (models.FIFOQueue), like knossos's queue model effectively does for
#: all but tiny histories.
UQ_MAX_VALUES = 31  # ids 1..31 → bits 0..30, sign bit untouched


def unordered_queue_step(state, f, a, b):  # jt: traced jaxpr(dot_generals<=0, dtype=int32)
    """Bag of unique values as a bitset.  (oracle: models.UnorderedQueue
    restricted to multiplicity ≤ 1)"""
    bit = jnp.int32(1) << (a.astype(jnp.int32) - 1)
    present = (state & bit) != 0
    is_enq = f == F_ENQUEUE
    is_deq = f == F_DEQUEUE
    ok = (is_enq & ~present) | (is_deq & present)
    state2 = jnp.where(
        is_enq, state | bit, jnp.where(is_deq, state & ~bit, state)
    ).astype(state.dtype)
    return state2, ok


@dataclass(frozen=True)
class ColumnarOps:
    """The columnar twin of a spec's ``encode_op``, run over a whole
    chunk of ops at once (:func:`jepsen_tpu.ops.encode.encode_chunk`).

    ``fs`` names the op functions it reads (at most 14), each coded by
    its position (-1 for any other f); the spec's ``pure_fs`` are among
    them.
    ``encode(f, values, none)`` maps the ops' f codes, propagated
    values (an object array) and which of those are None to ``(kernel
    f codes, a-values, b-values, undecided)``: object arrays of what
    ``encode_op`` would intern, a then b (None for V_UNKNOWN), and the
    ops it leaves to ``encode_op``, whose histories take the
    per-history path (the ops ``encode_op`` refuses among them)."""

    fs: Tuple[str, ...]
    encode: Callable


@dataclass(frozen=True)
class ModelSpec:
    """Host-side description of how a model maps onto the kernel."""

    name: str
    step: Callable  # (state, f, a, b) -> (state', ok), broadcastable
    #: encode an op (with completion value already propagated) into
    #: (f, a, b) int codes, given a mutable value→id map
    encode_op: Callable[[Any, Dict[Any, int]], Tuple[int, int, int]]
    #: initial kernel state from the oracle model instance
    init_state: Callable[[m.Model, Dict[Any, int]], int]
    #: fs that never change state — indeterminate ones are stripped
    pure_fs: Tuple[str, ...]
    #: True when only the dense automaton exists for this spec (its
    #: state enumeration is built from host tables the branchless step
    #: functions can't express); outside the dense envelope such
    #: batches go straight to the oracle, never the frontier kernel
    dense_only: bool = False
    #: the columnar twin of ``encode_op``; None keeps the spec on the
    #: per-history encoder
    encode_columns: Optional[ColumnarOps] = None


def _value_id(value, valmap: Dict[Any, int]) -> int:
    if value is None:
        return V_UNKNOWN
    vid = valmap.get(value)
    if vid is None:
        vid = len(valmap) + 1  # ids are 1-based; 0 is V_UNKNOWN
        valmap[value] = vid
    return vid


def _encode_register_op(op, valmap) -> Tuple[int, int, int]:
    if op.f == "write":
        return F_WRITE, _value_id(op.value, valmap), 0
    if op.f == "read":
        if op.value is None:
            return F_READ_ANY, 0, 0
        return F_READ, _value_id(op.value, valmap), 0
    raise ValueError(f"register cannot encode op f={op.f!r}")


def _encode_cas_op(op, valmap) -> Tuple[int, int, int]:
    if op.f == "cas":
        if op.value is None:
            raise ValueError("cas with nil value is never linearizable")
        old, new = op.value
        return F_CAS, _value_id(old, valmap), _value_id(new, valmap)
    return _encode_register_op(op, valmap)


def is_none(values: list) -> np.ndarray:
    """Which items of a list are None (by identity: a value's own
    ``==`` may not answer a bool)."""
    return np.frombuffer(bytes(map(is_, values, repeat(None))), bool)


def _register_columns(f, values, none, cas: bool):
    """Columnar twin of :func:`_encode_register_op` (``cas`` False) and
    :func:`_encode_cas_op` (True); ``f`` codes index
    ``REGISTER_COLUMNS.fs``, which equal F_READ, F_WRITE and F_CAS."""
    n = len(f)
    fk = f.astype(np.int8)
    fk[(f == F_READ) & none] = F_READ_ANY
    a = values.copy()
    b = np.full((n,), None, object)
    is_cas = f == F_CAS
    # encode_op refuses an unknown f, a plain register's cas and a nil
    # cas (never linearizable)
    undecided = (f < 0) | (is_cas & (none | (not cas)))
    if not cas:
        return fk, a, b, undecided
    ci = np.flatnonzero(is_cas & ~none)
    pairs = values[ci].tolist()
    # ``old, new = op.value`` unpacks any 2-iterable; read lists and
    # tuples of two here and leave the rest to encode_op
    if not (set(map(type, pairs)) <= {list, tuple}
            and set(map(len, pairs)) <= {2}):
        shaped = np.fromiter(
            (type(v) in (list, tuple) and len(v) == 2 for v in pairs),
            bool, len(pairs),
        )
        undecided[ci[~shaped]] = True
        ci = ci[shaped]
        pairs = [v for v, ok in zip(pairs, shaped.tolist()) if ok]
    # fromiter: never a nested array, whatever the values hold
    a[ci] = np.fromiter(map(itemgetter(0), pairs), object, len(pairs))
    b[ci] = np.fromiter(map(itemgetter(1), pairs), object, len(pairs))
    return fk, a, b, undecided


REGISTER_COLUMNS = ColumnarOps(fs=("read", "write", "cas"),
                               encode=partial(_register_columns, cas=False))
CAS_REGISTER_COLUMNS = ColumnarOps(fs=("read", "write", "cas"),
                                   encode=partial(_register_columns, cas=True))


def _encode_mutex_op(op, valmap) -> Tuple[int, int, int]:
    if op.f == "acquire":
        return F_ACQUIRE, 0, 0
    if op.f == "release":
        return F_RELEASE, 0, 0
    raise ValueError(f"mutex cannot encode op f={op.f!r}")


def _owner_client(op):
    # the oracle's identity extraction (models.locks._client) is the
    # single source of truth: encoder and oracle MUST agree on WHO
    # acted or device and oracle verdicts diverge
    from ..models.locks import _client

    client = _client(op)
    if client is None:
        # an op that never reported WHO acted (e.g. a crashed acquire
        # whose client died before stamping) cannot ride the value
        # automaton; the whole history falls back to the oracle
        raise ValueError("owner-mutex op without client identity")
    return client


def _rm_client_id(client, valmap: Dict[Any, int]) -> int:
    """1-based client index (the reentrant encoder interns nothing
    else, so _value_id stays contiguous over clients); the state
    domain is 2·N+1 ids for N clients (see reentrant_mutex_step)."""
    return _value_id(("rm-client", client), valmap)


def _encode_reentrant_mutex_op(op, valmap) -> Tuple[int, int, int]:
    """Reentrant mutex ops: a = client index; the step function owns
    the (free / once / twice) state algebra.  Only the reference's
    hold bound of 2 has a kernel; other bounds ride the oracle (the
    spec's init_state raises)."""
    client = _owner_client(op)
    cid = _rm_client_id(client, valmap)
    if op.f == "acquire":
        return F_RACQUIRE, cid, 0
    if op.f == "release":
        return F_RRELEASE, cid, 0
    raise ValueError(f"reentrant-mutex cannot encode op f={op.f!r}")


def _reentrant_mutex_init(model, valmap) -> int:
    from ..models.locks import REENTRANT_ACQUIRE_COUNT

    if model.max_count != REENTRANT_ACQUIRE_COUNT:
        raise ValueError(
            "reentrant-mutex kernel supports the hold bound of "
            f"{REENTRANT_ACQUIRE_COUNT} only"
        )
    if model.owner is None:
        return 0
    if model.count not in (1, 2):
        # a held owner with a count outside the algebra (count=0 is
        # constructible) has no state id — oracle fallback, not a
        # silently-diverging kernel verdict
        raise ValueError("reentrant-mutex init outside the kernel algebra")
    cid = _rm_client_id(model.owner, valmap)
    return 2 * cid - 1 if model.count == 1 else 2 * cid


def _pm_client_id(client, valmap: Dict[Any, int]) -> int:
    """1-based client index for the permit automaton (the permits
    encoder interns nothing else, so _value_id stays contiguous)."""
    return _value_id(("pm-client", client), valmap)


def _encode_permits_op(op, valmap) -> Tuple[int, int, int]:
    """Semaphore permit ops: a = client index.  The state enumeration
    (multisets of ≤ n_permits client ids) lives in host tables built by
    the dense kernel (ops/dense.py permits_tables); no branchless step
    function exists, so the spec is dense_only."""
    client = _owner_client(op)
    cid = _pm_client_id(client, valmap)
    if op.f == "acquire":
        return F_PACQUIRE, cid, 0
    if op.f == "release":
        return F_PRELEASE, cid, 0
    raise ValueError(f"acquired-permits cannot encode op f={op.f!r}")


def _permits_init(model, valmap) -> int:
    if model.acquired:
        # a non-empty initial multiset needs the global state
        # enumeration, which depends on the final client count the
        # encoder can't know yet — oracle fallback
        raise ValueError("acquired-permits kernel needs an empty start")
    return 0


def _no_step(state, f, a, b):  # pragma: no cover — gated by dense_only
    raise NotImplementedError(
        "acquired-permits has no frontier step; dense_only batches "
        "outside the dense envelope must go to the oracle"
    )


def _encode_owner_mutex_op(op, valmap) -> Tuple[int, int, int]:
    """The owner-aware mutex IS a cas-register in disguise: state =
    holder ("free" is its own value id), acquire(c) = cas(free → c),
    release(c) = cas(c → free) — so the whole cas-register kernel
    family (dense subset automaton included) applies unchanged.  Client
    identities ride the value-id map like register values."""
    client = _owner_client(op)
    free = _value_id("__free__", valmap)
    cid = _value_id(("client", client), valmap)
    if op.f == "acquire":
        return F_CAS, free, cid
    if op.f == "release":
        return F_CAS, cid, free
    raise ValueError(f"owner-mutex cannot encode op f={op.f!r}")


def _owner_mutex_init(model, valmap) -> int:
    if model.owner is None:
        return _value_id("__free__", valmap)
    return _value_id(("client", model.owner), valmap)


def _register_init(model, valmap) -> int:
    return _value_id(model.value, valmap)


def _mr_reg_id(k, valmap: Dict[Any, int]) -> int:
    """Register index for key k; at most MR_REGISTERS distinct keys."""
    key = ("mrreg", k)
    r = valmap.get(key)
    if r is None:
        r = valmap.get("__mr_nreg__", 0)
        if r >= MR_REGISTERS:
            raise ValueError("too many registers for the packed kernel")
        valmap[key] = r
        valmap["__mr_nreg__"] = r + 1
    return r


def _mr_value_id(reg: int, v, valmap: Dict[Any, int]) -> int:
    """Per-register value ids so each stays within MR_VALUE_BITS."""
    if v is None:
        return V_UNKNOWN
    key = ("mrval", reg, v)
    vid = valmap.get(key)
    if vid is None:
        nkey = ("mrn", reg)
        vid = valmap.get(nkey, 0) + 1
        if vid > MR_MAX_VALUE_ID:
            raise ValueError("too many distinct values for one register")
        valmap[key] = vid
        valmap[nkey] = vid
    return vid


def _encode_multi_register_op(op, valmap) -> Tuple[int, int, int]:
    """Single-mop [(f, k, v)] transactions; multi-mop ones fall back to
    the oracle (models.MultiRegister handles arbitrary mop lists)."""
    mops = list(op.value or [])
    if not mops:
        return F_READ_ANY, 0, 0
    if len(mops) != 1:
        raise ValueError("multi-mop transactions ride the oracle")
    mf, k, v = mops[0]
    reg = _mr_reg_id(k, valmap)
    if mf in ("w", "write"):
        if v is None:
            raise ValueError("write of nil is never linearizable")
        return F_WRITE, _mr_value_id(reg, v, valmap), reg
    if mf in ("r", "read"):
        if v is None:
            return F_READ_ANY, 0, reg
        return F_READ, _mr_value_id(reg, v, valmap), reg
    raise ValueError(f"multi-register cannot encode mop f={mf!r}")


def _mr_init(model, valmap) -> int:
    state = 0
    for k, v in dict(model.values).items():
        reg = _mr_reg_id(k, valmap)
        vid = _mr_value_id(reg, v, valmap)
        state |= vid << (reg * MR_VALUE_BITS)
    return state


def _uq_value_id(v, valmap: Dict[Any, int]) -> int:
    """Namespaced ids with their own counter (like _mr_value_id) —
    sharing _value_id's len(valmap)-based counter would double-count
    the bookkeeping keys below and halve the usable envelope."""
    if v is None:
        raise ValueError("queue op with unknown value rides the oracle")
    key = ("uqval", v)
    vid = valmap.get(key)
    if vid is None:
        vid = valmap.get("__uq_n__", 0) + 1
        if vid > UQ_MAX_VALUES:
            raise ValueError(
                "too many distinct values for the bitset kernel"
            )
        valmap[key] = vid
        valmap["__uq_n__"] = vid
    return vid


def _encode_unordered_queue_op(op, valmap) -> Tuple[int, int, int]:
    if op.f == "enqueue":
        vid = _uq_value_id(op.value, valmap)
        key = ("uq-enq", vid)
        if valmap.get(key):
            raise ValueError(
                "value enqueued more than once; multiset histories ride "
                "the oracle"
            )
        valmap[key] = 1
        return F_ENQUEUE, vid, 0
    if op.f == "dequeue":
        return F_DEQUEUE, _uq_value_id(op.value, valmap), 0
    raise ValueError(f"unordered-queue cannot encode op f={op.f!r}")


def _uq_init(model, valmap) -> int:
    state = 0
    for v, count in dict(model.items).items():
        if count != 1:
            raise ValueError("initial multiplicities >1 ride the oracle")
        vid = _uq_value_id(v, valmap)
        valmap[("uq-enq", vid)] = 1  # counts against the once-only rule
        state |= 1 << (vid - 1)
    return state


SPECS: Dict[type, ModelSpec] = {
    m.Register: ModelSpec(
        name="register",
        step=register_step,
        encode_op=_encode_register_op,
        init_state=_register_init,
        pure_fs=("read",),
        encode_columns=REGISTER_COLUMNS,
    ),
    m.CASRegister: ModelSpec(
        name="cas-register",
        step=cas_register_step,
        encode_op=_encode_cas_op,
        init_state=_register_init,
        pure_fs=("read",),
        encode_columns=CAS_REGISTER_COLUMNS,
    ),
    m.Mutex: ModelSpec(
        name="mutex",
        step=mutex_step,
        encode_op=_encode_mutex_op,
        init_state=lambda model, valmap: 1 if model.locked else 0,
        pure_fs=(),
    ),
    m.MultiRegister: ModelSpec(
        name="multi-register",
        step=multi_register_step,
        encode_op=_encode_multi_register_op,
        init_state=_mr_init,
        pure_fs=(),
    ),
    m.UnorderedQueue: ModelSpec(
        name="unordered-queue",
        step=unordered_queue_step,
        encode_op=_encode_unordered_queue_op,
        init_state=_uq_init,
        pure_fs=(),
    ),
    # the owner-aware mutex reduces to cas-register ops at encode time
    # (_encode_owner_mutex_op) and reuses that step function, so the
    # whole kernel family — including the overflow-free dense subset
    # automaton — applies without a new device step.  The name stays
    # unique (wgl resolves specs BY name).  The fenced/reentrant/
    # permit flavors carry state the value automaton can't express
    # (global fence monotonicity, hold counts, multisets) and stay
    # oracle-checked.
    m.OwnerMutex: ModelSpec(
        name="owner-mutex",
        step=cas_register_step,
        encode_op=_encode_owner_mutex_op,
        init_state=_owner_mutex_init,
        pure_fs=(),
    ),
    # reentrant owner-aware mutex (hold bound 2): its own step algebra
    # over state ids {0, 2c-1, 2c}; the state DOMAIN is 2·N+1 for N
    # clients — check_batch widens n_values accordingly.  The fenced
    # flavors stay oracle-only (global fence monotonicity over
    # unbounded tokens has no small value automaton).
    m.ReentrantMutex: ModelSpec(
        name="reentrant-mutex",
        step=reentrant_mutex_step,
        encode_op=_encode_reentrant_mutex_op,
        init_state=_reentrant_mutex_init,
        pure_fs=(),
    ),
    # semaphore permits: a multiset of ≤ n_permits client ids — the
    # state enumeration comes from host-precomputed transition tables
    # (ops/dense.py permits_tables), so only the dense automaton
    # exists; past its envelope the oracle takes the batch
    m.AcquiredPermits: ModelSpec(
        name="acquired-permits",
        step=_no_step,
        encode_op=_encode_permits_op,
        init_state=_permits_init,
        pure_fs=(),
        dense_only=True,
    ),
}


def spec_for(model: m.Model) -> Optional[ModelSpec]:
    return SPECS.get(type(model))
