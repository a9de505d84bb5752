"""The columnar chunk encoder (ops/encode.py ``encode_chunk``) against
the per-history encoders: every history of a chunk must encode array
for array as ``_encode_history_loop`` and ``encode_history`` encode it
alone, ``None`` (the oracle route) included, whichever path the chunk
sent it down."""

import importlib.util
import json
import os
import random

import numpy as np
import pytest

from jepsen_tpu import independent
from jepsen_tpu import models as m
from jepsen_tpu.history import (History, Op, fail_op, info_op, invoke_op,
                                ok_op)
from jepsen_tpu.ops import encode
from jepsen_tpu.ops.step_kernels import spec_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
FIELDS = ("ev_slot", "cand_slot", "cand_f", "cand_a", "cand_b")


def h(*ops) -> History:
    return History(ops).index_ops()


def _generator():
    path = os.path.join(PERF, "generators", "cas_register.py")
    spec = importlib.util.spec_from_file_location("perf_gen_cas", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


def _cell_pool(config, traffic, cell, seed):
    """The cell's traffic pool at its test size (perf/tests/shrink)."""
    shrink = _read("tests", "shrink", cell + ".json")
    cfg = dict(_read("configs", config + ".json"), **shrink.get("config", {}))
    tr = dict(_read("traffic", traffic + ".json"), **shrink.get("traffic", {}))
    return _generator().make_pool(cfg, tr, seed)


def batch1024_pool():
    """``cas1k_batch``'s histories: every request of the pool."""
    pool = _cell_pool("cas_register_1k", "batch1024", "cas1k_batch", 2800001)
    return [h(*(Op(t, p, f, v) for t, p, f, v in events))
            for req in pool for events in req]


def keyed_ladder_pool():
    """``keyed_test``'s per-key sub-histories, as the keyed checker
    splits its tests."""
    out = []
    for (keys,) in _cell_pool("keyed_register", "keyed_ladder", "keyed_test",
                              2800002):
        test = h(*(Op(t, p, f, independent.kv(k, v))
                   for k, events in keys for t, p, f, v in events))
        by_key = independent.subhistories(test)
        out += [by_key[k].client_ops().index_ops()
                for k in sorted(by_key, key=str)]
    return out


def random_history(rng, cas: bool, n_procs: int, n_ops: int) -> History:
    """A concurrent register history with crashes, fails, crashed pure
    reads, nemesis ops, ops left open at the end, and reads whose
    invocations carry no value."""
    fs = ["read", "write", "cas"] if cas else ["read", "write"]
    ops, pending, done = [], {}, 0
    idle = list(range(n_procs))
    next_p = n_procs
    while done < n_ops or pending:
        if rng.random() < 0.03:
            ops.append(info_op("nemesis", rng.choice(["start", "stop"])))
        if idle and done < n_ops and (not pending or rng.random() < 0.55):
            p = idle.pop(rng.randrange(len(idle)))
            f = rng.choice(fs)
            v = (None if f == "read" else rng.randint(0, 4) if f == "write"
                 else [rng.randint(0, 4), rng.randint(0, 4)])
            ops.append(invoke_op(p, f, v))
            pending[p] = (f, v)
            done += 1
            continue
        if done >= n_ops and rng.random() < 0.05:
            break  # leave the rest open forever
        p = rng.choice(list(pending))
        f, v = pending.pop(p)
        u = rng.random()
        if u < 0.08:
            ops.append(info_op(p, f, v if f != "read" else None))
            idle.append(next_p)  # a crashed process is replaced
            next_p += 1
            continue
        if u < 0.18 and f != "read":
            ops.append(fail_op(p, f, v))
        else:
            ops.append(ok_op(p, f, rng.randint(0, 4) if f == "read" else v))
        idle.append(p)
    return h(*ops)


def random_pool(seed: int, cas: bool):
    rng = random.Random(seed)
    return [random_history(rng, cas, rng.randint(1, 9),
                           rng.choice([0, 1, 3, 12, 40, 150]))
            for _ in range(40)]


def edge_histories():
    """(history, expected path) pairs the columnar pass must not get
    wrong: malformed ones go down the per-history path."""
    ok_pair = [invoke_op(0, "write", 1), ok_op(0, "write", 1)]
    return [
        # a process invoking twice without completing
        (h(invoke_op(0, "write", 1), invoke_op(0, "write", 2),
           ok_op(0, "write", 2)), "per_history"),
        # a completion with no open invoke
        (h(ok_op(1, "read", 3), *ok_pair), "per_history"),
        # more ops open at once than slot_cap (16 here)
        (h(*[invoke_op(p, "write", p % 5) for p in range(17)]), "columnar"),
        # exactly slot_cap open at once
        (h(*[invoke_op(p, "write", p % 5) for p in range(16)],
           ok_op(3, "write", 3)), "columnar"),
        # a cas with no value never linearizes: encode_op refuses it
        (h(invoke_op(0, "cas", None), ok_op(0, "cas", None)),
         "per_history"),
        # a cas value the columnar pass does not read
        (h(invoke_op(0, "cas", "ab"), ok_op(0, "cas", "ab")),
         "per_history"),
        # an unknown f: encode_op refuses it
        (h(invoke_op(0, "incr", 1), ok_op(0, "incr", 1)), "per_history"),
        # a failed unknown f never happened: encodes
        (h(invoke_op(0, "incr", 1), fail_op(0, "incr", 1), *ok_pair),
         "columnar"),
        # a crashed read (pure) is dropped, a crashed write stays open
        (h(invoke_op(0, "read"), invoke_op(1, "write", 2),
           info_op(0, "read"), info_op(1, "write", 2), *ok_pair),
         "columnar"),
        # nothing but invocations, and nothing at all
        (h(invoke_op(0, "write", 1), invoke_op(1, "read")), "columnar"),
        (h(), "columnar"),
        # read of None: READ_ANY; write of None: value id 0
        (h(invoke_op(0, "read"), ok_op(0, "read"), invoke_op(1, "write"),
           ok_op(1, "write")), "columnar"),
        # as many distinct values as the int16 lanes hold (the init
        # value 0 among them), and one more
        (h(*_writes(encode.MAX_VALUE_ID - 1)), "columnar"),
        (h(*_writes(encode.MAX_VALUE_ID)), "columnar"),
        # linear.prepare reads no event of a process that is no int
        # (a numpy integer, a float) nor of another type; a bool is one
        (h(invoke_op(np.int64(2), "write", 3), *ok_pair,
           ok_op(np.int64(2), "write", 3), invoke_op(2.0, "read"),
           Op("weird", 1, "read", 4), invoke_op(True, "read"),
           ok_op(1, "read", 1)), "columnar"),
    ]


def _writes(n):
    """``n`` ok writes of the values 1..n, one after another."""
    return [op for v in range(1, n + 1)
            for op in (invoke_op(0, "write", v), ok_op(0, "write", v))]


def _assert_same(got, ref, where):
    assert (got is None) == (ref is None), where
    if ref is None:
        return
    assert got.init_state == ref.init_state, where
    assert got.n_ops == ref.n_ops, where
    assert got.max_open == ref.max_open, where
    for name in FIELDS:
        a, b = getattr(got, name), getattr(ref, name)
        assert a.dtype == b.dtype and a.shape == b.shape, (where, name)
        assert np.array_equal(a, b), (where, name)


def _check_chunk(hists, models, slot_cap):
    spec = spec_for(models[0])
    got, per_history = encode.encode_chunk(hists, models, slot_cap, spec)
    assert len(got) == len(hists)
    for i, (e, hist, model) in enumerate(zip(got, hists, models)):
        _assert_same(e, encode._encode_history_loop(hist, model, slot_cap,
                                                    spec), i)
        _assert_same(e, encode.encode_history(hist, model, slot_cap, spec), i)
    return got, per_history


POOLS = {
    "batch1024": (batch1024_pool, lambda: m.cas_register(0), 32),
    "keyed_ladder": (keyed_ladder_pool, lambda: m.cas_register(0), 16),
    "random_cas": (lambda: random_pool(11, True), lambda: m.cas_register(0),
                   32),
    "random_cas_init_none": (lambda: random_pool(12, True),
                             lambda: m.cas_register(None), 8),
    "random_register": (lambda: random_pool(13, False),
                        lambda: m.register(2), 32),
    # a register meets cas ops, which encode_op refuses
    "register_meets_cas": (lambda: random_pool(14, True),
                           lambda: m.register(0), 32),
}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_chunk_equals_per_history_encoders(pool):
    make, model, slot_cap = POOLS[pool]
    hists = make()
    rng = random.Random(pool)
    rng.shuffle(hists)  # mixed lengths side by side in one chunk
    got, per_history = _check_chunk(hists, [model()] * len(hists), slot_cap)
    assert any(e is not None for e in got)
    if pool == "register_meets_cas":
        # the histories with a cas take encode_op, which refuses it
        assert per_history and all(got[i] is None for i in per_history)
    else:
        assert per_history == []  # every history took the columnar pass


def test_chunk_edge_cases_in_one_chunk():
    cases = edge_histories()
    filler = random_pool(15, True)[:10]
    hists = [c for c, _ in cases] + filler
    model = m.cas_register(0)
    got, per_history = _check_chunk(hists, [model] * len(hists), 16)
    expect = [i for i, (_, path) in enumerate(cases) if path == "per_history"]
    assert per_history == expect
    # overflow, nil cas, unknown f and too many values ride the oracle;
    # the columnar pass decides the first and the last
    assert [i for i, e in enumerate(got[:len(cases)]) if e is None] == [
        2, 4, 6, 13]


def test_chunk_per_history_models_and_other_specs():
    """Each history's own model seeds its init state; a spec with no
    columnar encoder takes the per-history path whole."""
    hists = random_pool(16, True)[:12]
    models = [m.cas_register(i % 3) for i in range(len(hists))]
    got, per_history = _check_chunk(hists, models, 32)
    assert per_history == []
    mutex = [h(invoke_op(0, "acquire"), ok_op(0, "acquire"),
               invoke_op(1, "release"), ok_op(1, "release"))] * 3
    got, per_history = _check_chunk(mutex, [m.mutex()] * 3, 32)
    assert per_history == [0, 1, 2]
    assert all(e is not None for e in got)


@pytest.mark.parametrize("slot_pass", ["walk", "rank_steps"])
@pytest.mark.parametrize("pool", ["batch1024", "keyed_ladder", "random_cas"])
def test_slot_pass_walk_equals_rank_steps(monkeypatch, pool, slot_pass):
    """Both ways of handing out slots, one event at a time or one numpy
    step a rank across the chunk's histories, give the encoders'
    arrays, whatever shape the chunk has."""
    monkeypatch.setattr(encode, "_RANK_STEP_EVENTS",
                        10**9 if slot_pass == "walk" else 0)
    monkeypatch.setattr(encode, "_PASS_COST_EVENTS", 0)
    make, model, slot_cap = POOLS[pool]
    hists = make()
    for chunk in (hists[:1], hists[:3], hists):
        got, per_history = _check_chunk(chunk, [model()] * len(chunk),
                                        slot_cap)
        if len(chunk) > 1:
            assert per_history == []


@pytest.mark.parametrize("shape,path", [
    ((1, 10), "per_history"), ((1, 100), "per_history"),
    ((2, 30), "per_history"), ((1, 1000), "columnar"),
    ((4, 30), "columnar"), ((16, 20), "columnar"),
])
def test_a_chunk_too_small_for_the_pass_takes_per_history(shape, path):
    """A chunk whose events cannot pay for the columnar pass's fixed
    cost (one or two short histories: a single test, a daemon's few
    new rows) takes encode_history whole; the arrays are the same."""
    n, n_ops = shape
    rng = random.Random(n * n_ops)
    hists = [random_history(rng, True, 5, n_ops) for _ in range(n)]
    got, per_history = _check_chunk(hists, [m.cas_register(0)] * n, 32)
    assert per_history == (list(range(n)) if path == "per_history" else [])


@pytest.mark.parametrize("field", ["f", "value", "process"])
def test_an_unreadable_field_sends_the_chunk_per_history(field):
    """An unhashable f or value, or a process past int64, is met by
    encode_history as it always was: the whole chunk takes it, and the
    chunk raises or encodes exactly as encode_history does."""
    filler = random_pool(17, True)[:8]
    odd = {"f": h(invoke_op(0, ["read"]), ok_op(0, ["read"], 1)),
           "value": h(invoke_op(0, "write", [1]), ok_op(0, "write", [1])),
           "process": h(invoke_op(2**70, "write", 1),
                        ok_op(2**70, "write", 1))}[field]
    hists = filler + [odd]
    model = m.cas_register(0)
    spec = spec_for(model)
    try:
        want = [encode.encode_history(x, model, 32, spec) for x in hists]
    except TypeError:
        with pytest.raises(TypeError):
            encode.encode_chunk(hists, [model] * len(hists), 32, spec)
        return
    got, per_history = _check_chunk(hists, [model] * len(hists), 32)
    assert per_history == list(range(len(hists)))
    assert [e is None for e in got] == [e is None for e in want]
