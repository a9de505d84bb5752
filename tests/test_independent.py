"""Tests for jepsen_tpu.independent (reference: independent.clj +
test/jepsen/independent_test.clj behaviors)."""

import pytest

from jepsen_tpu import generator as gen
from jepsen_tpu import independent as ind
from jepsen_tpu import models
from jepsen_tpu.generator import sim
from jepsen_tpu.history import History, Op, invoke_op, ok_op
from jepsen_tpu.checker import Checker


def test_kv_tuple():
    t = ind.kv("k", 3)
    assert ind.is_tuple(t)
    assert t.key == "k"
    assert t.value == 3
    assert not ind.is_tuple(("k", 3))
    assert t == ("k", 3)  # still a tuple


def test_sequential_generator_wraps_values():
    g = ind.sequential_generator([0, 1], lambda k: [{"f": "read"}] * 2)
    h = sim.quick(g, ctx=sim.n_plus_nemesis_context(1))
    vals = [o["value"] for o in h]
    assert all(ind.is_tuple(v) for v in vals)
    assert [v.key for v in vals] == [0, 0, 1, 1]


def test_concurrent_generator_groups():
    # 4 client threads, 2 per key => 2 concurrent keys
    g = ind.concurrent_generator(
        2, list(range(4)), lambda k: [{"f": "read"}] * 4
    )
    h = sim.quick(g, ctx=sim.n_plus_nemesis_context(4))
    keys = [o["value"].key for o in h]
    assert len(h) == 16
    # first two keys run concurrently before later ones appear
    first_half = set(keys[:8])
    assert first_half == {0, 1}
    assert set(keys) == {0, 1, 2, 3}


def test_concurrent_generator_rejects_bad_concurrency():
    g = ind.concurrent_generator(3, [0], lambda k: [{"f": "read"}])
    with pytest.raises(Exception):
        sim.quick(g, ctx=sim.n_plus_nemesis_context(4))


def test_history_keys_and_subhistory():
    h = History(
        [
            invoke_op(0, "read", ind.kv(1, None), time=0, index=0),
            Op("info", "nemesis", "start", None, time=1, index=1),
            ok_op(0, "read", ind.kv(1, 5), time=2, index=2),
            invoke_op(1, "write", ind.kv(2, 7), time=3, index=3),
            ok_op(1, "write", ind.kv(2, 7), time=4, index=4),
        ]
    )
    assert ind.history_keys(h) == {1, 2}
    sub = ind.subhistory(1, h)
    assert [op.value for op in sub] == [None, None, 5]
    # nemesis op appears in every subhistory
    assert any(op.process == "nemesis" for op in ind.subhistory(2, h))


def _nemesis(f, time):
    return Op("info", "nemesis", f, None, time=time)


# Histories for the one-walk split, each against subhistory per key.
_SPLIT_CASES = {
    "nemesis_before_between_after": [
        _nemesis("start", 0),
        invoke_op(0, "write", ind.kv(1, 3), time=1),
        _nemesis("stop", 2),
        ok_op(0, "write", ind.kv(1, 3), time=3),
        invoke_op(1, "read", ind.kv(2, None), time=4),
        ok_op(1, "read", ind.kv(2, 0), time=5),
        _nemesis("start", 6),
    ],
    "key_first_seen_after_nemesis": [
        invoke_op(0, "write", ind.kv("a", 1), time=0),
        ok_op(0, "write", ind.kv("a", 1), time=1),
        _nemesis("kill", 2),
        invoke_op(1, "cas", ind.kv("b", [0, 2]), time=3),
        Op("info", 1, "cas", ind.kv("b", [0, 2]), time=4, error="timeout"),
    ],
    "unkeyed_client_op": [
        invoke_op(0, "write", ind.kv("a", 1), time=0),
        invoke_op(1, "read", None, time=1),
        ok_op(0, "write", ind.kv("a", 1), time=2),
        ok_op(1, "read", 4, time=3),
        invoke_op(2, "read", ind.kv("b", None), time=4),
        ok_op(2, "read", ind.kv("b", 0), time=5),
    ],
    "empty": [],
}


@pytest.mark.parametrize("case", sorted(_SPLIT_CASES))
def test_subhistories_match_subhistory(case):
    h = History(_SPLIT_CASES[case]).index_ops()
    before = h.to_dicts()
    got = ind.subhistories(h)
    want = {k: ind.subhistory(k, h) for k in ind.history_keys(h)}
    assert {k: s.to_dicts() for k, s in got.items()} == {
        k: s.to_dicts() for k, s in want.items()
    }
    # every op is the sub-history's own: re-indexing one touches
    # neither the others nor the history it came from
    for s in got.values():
        s.index_ops()
    assert h.to_dicts() == before
    assert len({id(op) for s in got.values() for op in s}) == sum(
        len(s) for s in got.values()
    )


class _ValueChecker(Checker):
    """Valid iff every ok op's value is even."""

    def check(self, test, history, opts=None):
        bad = [op.value for op in history if op.is_ok and op.value % 2]
        return {"valid?": not bad, "bad": bad}


def test_independent_checker():
    h = History(
        [
            invoke_op(0, "w", ind.kv("a", 2), time=0),
            ok_op(0, "w", ind.kv("a", 2), time=1),
            invoke_op(0, "w", ind.kv("b", 3), time=2),
            ok_op(0, "w", ind.kv("b", 3), time=3),
        ]
    ).index_ops()
    chk = ind.checker(_ValueChecker())
    res = chk.check({"name": "t", "store?": False}, h, {})
    assert res["valid?"] is False
    assert res["failures"] == ["b"]
    assert res["results"]["a"]["valid?"] is True


def _register_history(k, values_ok=True):
    """A tiny per-key linearizable (or not) register history."""
    ops = [
        invoke_op(0, "write", ind.kv(k, 1), time=0),
        ok_op(0, "write", ind.kv(k, 1), time=1),
        invoke_op(1, "read", ind.kv(k, None), time=2),
        ok_op(1, "read", ind.kv(k, 1 if values_ok else 9), time=3),
    ]
    return ops


def test_batched_linearizable():
    ops = _register_history("good") + _register_history("bad", values_ok=False)
    # adjust times so ops interleave but remain per-key sane
    h = History(ops).index_ops()
    chk = ind.batched_linearizable(models.cas_register())
    res = chk.check({"name": "t", "store?": False}, h, {})
    assert res["results"]["good"]["valid?"] is True
    assert res["results"]["bad"]["valid?"] is False
    assert res["failures"] == ["bad"]
    assert res["valid?"] is False
    # the engine/kernel breakdown rides the result so keyspace routing
    # drift is visible in results.json
    stats = res["batch-stats"]
    assert stats["engines"].get("tpu") == 2, stats
    assert stats["device-rate"] == 1.0 and stats["oracle-rate"] == 0.0


def test_batched_linearizable_split_matches_per_key_split():
    """The one-walk split hands the engine what one subhistory walk per
    key did: same keys in the same order, client ops only, each indexed
    0..n-1; so the verdicts match, key for key, on a history with a
    corrupted key and a nemesis op."""
    from jepsen_tpu.ops import wgl

    ops = []
    for k in ("k0", "k1", "k2"):
        ops += _register_history(k, values_ok=(k != "k1"))
    ops.insert(5, _nemesis("start", 2))
    h = History(ops).index_ops()
    model = models.cas_register()
    res = ind.batched_linearizable(model).check(
        {"name": "t", "store?": False}, h, {}
    )
    ks = sorted(ind.history_keys(h), key=str)
    ref = wgl.check_batch(
        model, [ind.subhistory(k, h).client_ops().index_ops() for k in ks]
    )
    assert list(res["results"]) == ks
    assert {k: r["valid?"] for k, r in res["results"].items()} == {
        k: r["valid?"] for k, r in zip(ks, ref)
    } == {"k0": True, "k1": False, "k2": True}
    assert res["valid?"] is False and res["failures"] == ["k1"]


class _CountingHistory(History):
    """Counts the ops handed out by iterating it, over every walk."""

    visits = 0

    def __iter__(self):
        for op in super().__iter__():
            self.visits += 1
            yield op


@pytest.mark.parametrize(
    "split",
    [
        lambda h: ind.subhistories(h),
        lambda h: ind.checker(_ValueChecker()).check(
            {"name": "t", "store?": False}, h, {}
        ),
        lambda h: ind.batched_linearizable(models.cas_register()).check(
            {"name": "t", "store?": False}, h, {}
        ),
    ],
    ids=["subhistories", "checker", "batched_linearizable"],
)
def test_split_walks_a_keyed_history_a_constant_number_of_times(split):
    """Splitting 200 keys walks the history a fixed number of times, not
    once per key (a count of ops visited, not a wall-clock time)."""
    ops = []
    for k in range(200):
        ops += [invoke_op(k % 5, "write", ind.kv(k, 2), time=2 * k),
                ok_op(k % 5, "write", ind.kv(k, 2), time=2 * k + 1)]
    ops.insert(200, _nemesis("start", 200))
    h = _CountingHistory(ops).index_ops()
    h.visits = 0
    result = split(h)
    assert len(result) == 200 or result["valid?"] is True
    assert h.visits <= 2 * len(h), h.visits / len(h)


class _Recorder(Checker):
    """Valid; keeps every history it was handed."""

    def __init__(self):
        self.seen = []

    def check(self, test, history, opts=None):
        self.seen.append(history)
        return {"valid?": True}


def test_independent_checker_subhistories_own_their_unkeyed_ops():
    """An un-keyed op sits at a different position in each key's
    sub-history; each sub-history's numbering must stay its own while
    the keys are checked in parallel, and the input keeps its own."""
    h = History(
        [
            invoke_op(0, "write", ind.kv("a", 1), time=0),
            ok_op(0, "write", ind.kv("a", 1), time=1),
            _nemesis("start", 2),
            invoke_op(1, "write", ind.kv("b", 2), time=3),
            ok_op(1, "write", ind.kv("b", 2), time=4),
        ]
    ).index_ops()
    rec = _Recorder()
    res = ind.checker(rec).check({"name": "t", "store?": False}, h, {})
    assert res["valid?"] is True and len(rec.seen) == 2
    for sub in rec.seen:
        assert [op.index for op in sub] == list(range(len(sub)))
    assert [op.index for op in h] == list(range(5))


def test_concurrent_generator_infinite_lazy_keys():
    """The reference's independent-deadlock-case
    (generator_test.clj:440): concurrent-generator over an INFINITE
    lazy key sequence must stream keys on demand — materializing the
    sequence hung forever before round 5.  The schedule matches the
    reference: each 2-thread group drains one key per round."""
    import itertools

    g = gen.limit(
        5,
        ind.concurrent_generator(
            2, itertools.count(), lambda k: gen.each_thread({"f": "meow"})
        ),
    )
    out = sim.perfect(g)
    got = [
        (o["time"], o["f"], o["value"].key)
        for o in out
        if o["type"] == "invoke"
    ]
    assert got == [
        (0, "meow", 0),
        (0, "meow", 0),
        (10, "meow", 1),
        (10, "meow", 1),
        (20, "meow", 2),
    ]
