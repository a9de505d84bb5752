"""The plain reference against the generator's known verdicts and
against a brute-force search over every order of tiny histories."""

import itertools
import random

import cas_register as ref
import harness

gen = harness.cell("cas1k_batch").module("generator")


def brute_force(history, init=0):
    """Linearizable iff some subset of the crashed ops and some order
    of the chosen ops respects real time and the register's rules."""
    ops = []
    open_at = {}
    for i, (t, p, f, v) in enumerate(history):
        if t == "invoke":
            open_at[p] = len(ops)
            ops.append([i, None, f, v, None])
        else:
            o = ops[open_at.pop(p)]
            o[4] = t
            if t == "ok":
                # a crashed op never completes: only an ok bounds it
                o[1], o[3] = i, v
    must = [o for o in ops if o[4] == "ok"]
    maybe = [o for o in ops if o[4] in (None, "info") and o[2] != "read"]
    for r in range(len(maybe) + 1):
        for extra in itertools.combinations(maybe, r):
            chosen = must + list(extra)
            for perm in itertools.permutations(chosen):
                ok, state = True, init
                for a, b in itertools.combinations(range(len(perm)), 2):
                    # b after a in the order: b must not complete before
                    # a is invoked
                    if perm[b][1] is not None and perm[b][1] < perm[a][0]:
                        ok = False
                        break
                if not ok:
                    continue
                for _i, _c, f, v, _t in perm:
                    if f == "read":
                        if v != state:
                            ok = False
                            break
                    elif f == "write":
                        state = v
                    elif state == v[0]:
                        state = v[1]
                    else:
                        ok = False
                        break
                if ok:
                    return True
    return False


def test_agrees_with_brute_force():
    rng = random.Random(4)
    seen = {True: 0, False: 0}
    for _ in range(300):
        h = gen.single_history(rng, rng.choice([2, 3]), rng.choice([3, 4, 5]),
                               0.3, rng.random() < 0.5, 3,
                               replace_crashed=rng.random() < 0.5)
        want = brute_force(h)
        assert ref.linearizable(h) == want, h
        seen[want] += 1
    assert seen[True] > 50 and seen[False] > 20


def test_agrees_with_known_verdicts():
    rng = random.Random(8)
    n = 0
    for _ in range(150):
        h = gen.single_history(rng, 5, 200, rng.choice([0.002, 0.01, 0.05]),
                               rng.random() < 0.5, 5,
                               replace_crashed=rng.random() < 0.5)
        known = ref.known_verdict(h, 5)
        if known is not None:
            assert ref.linearizable(h) == known
            n += 1
    assert n > 100


def test_keyed_merges_per_key():
    import verify

    keys = [(0, [("invoke", 0, "write", 1), ("ok", 0, "write", 1)]),
            (1, [("invoke", 1, "read", None), ("ok", 1, "read", 8)])]
    want = verify.Expected(ref, {"values": 5, "init": 0},
                           {"shape": "keyed"})
    assert want.of("t", keys, audit=True) == {
        ("key", 0): True, ("key", 1): False, "valid?": False}
    assert want.reference_runs == 3 and want.disagreements == 0


def test_audit_is_counted_by_the_harness():
    """verify.py, not the reference, holds the search to what is known:
    a search that contradicts a known verdict counts on audit items,
    is not asked where the verdict is known, and the control's search
    is held to nothing."""
    import types

    import verify

    liar = types.SimpleNamespace(
        known=lambda cfg, tr, item: {"valid?": True, "other": None},
        search=lambda cfg, tr, item, labels, **_o: {lb: False
                                                    for lb in labels})
    want = verify.Expected(liar, {}, {})
    assert want.of("a", None) == {"valid?": True, "other": False}
    assert want.reference_runs == 1 and want.disagreements == 0
    assert want.of("b", None, audit=True) == {"valid?": False,
                                              "other": False}
    assert want.reference_runs == 3 and want.disagreements == 1
    control = verify.Expected(liar, {}, {}, crashed_take_effect=False)
    control.of("c", None)
    assert control.reference_runs == 2 and control.disagreements == 0


def test_crashed_ops_stay_open():
    h = [("invoke", 0, "write", 3), ("info", 0, "write", 3),
         ("invoke", 1, "read", None), ("ok", 1, "read", 0),
         ("invoke", 1, "read", None), ("ok", 1, "read", 3)]
    assert ref.linearizable(h) is True
    # the control: a crashed op that never takes effect
    assert ref.linearizable(h, crashed_take_effect=False) is False
