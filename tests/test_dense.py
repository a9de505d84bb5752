"""Dense subset-automaton kernel tests: golden histories, differential
fuzz vs the CPU oracle AND vs the generic frontier kernel, and envelope/
dispatch checks.

The dense kernel (jepsen_tpu.ops.dense) is the TPU fast path for the
register-family models the reference's linearizable checker runs
(jepsen/src/jepsen/checker.clj:19-26); it must agree exactly with the
oracle on every verdict — there is no "unknown" escape hatch to hide
behind, since the dense representation cannot overflow.
"""

import random

import numpy as np
import pytest

from jepsen_tpu import models as m
from jepsen_tpu.checker import linear
from jepsen_tpu.history import History, invoke_op, ok_op, fail_op, info_op
from jepsen_tpu.ops import dense, encode, wgl
from jepsen_tpu.synth import generate_history as _gen

from test_wgl import GOLDEN, h


def _dense_verdicts(model, hists, pure_fs):
    """Run histories through the dense kernel directly (no dispatch)."""
    batch = encode.batch_encode(hists, model)
    assert not batch.fallback
    E = batch.ev_slot.shape[1]
    C = batch.cand_slot.shape[2]
    V = encode.round_up(
        1 + int(max(batch.init_state.max(), batch.cand_a.max(), batch.cand_b.max())),
        4,
    )
    assert dense.applicable(_spec_name(model), C, V)
    fn = dense.make_dense_fn(_spec_name(model), E, C, V)
    ok, failed_at, overflow = fn(
        batch.init_state,
        batch.ev_slot,
        batch.cand_slot,
        batch.cand_f,
        batch.cand_a,
        batch.cand_b,
    )
    assert not bool(np.asarray(overflow).any())  # dense can never overflow
    out = [None] * len(hists)
    for row, hi in enumerate(batch.row_history):
        out[hi] = bool(np.asarray(ok)[row])
    return out


def _spec_name(model):
    from jepsen_tpu.ops.step_kernels import spec_for

    return spec_for(model).name


@pytest.mark.parametrize("case", range(len(GOLDEN)))
def test_golden_dense(case):
    model_fn, hist_fn, expected = GOLDEN[case]
    model = model_fn()
    spec = __import__(
        "jepsen_tpu.ops.step_kernels", fromlist=["spec_for"]
    ).spec_for(model)
    got = _dense_verdicts(model, [hist_fn()], spec.pure_fs)
    assert got == [expected]


def test_applicable_envelope():
    assert dense.applicable("cas-register", 8, 8)
    assert dense.applicable("mutex", 4, 4)
    assert not dense.applicable("cas-register", 16, 8)   # 2^16 subsets
    assert not dense.applicable("cas-register", 8, 64)   # value domain
    assert not dense.applicable("multi-register", 8, 8)  # packed state


def test_dispatch_prefers_dense():
    fn = wgl.make_best_check_fn("cas-register", 64, 8, 64, 9, n_values=6)
    assert fn is dense.make_dense_fn("cas-register", 64, 8, 8)
    # out-of-envelope value domains ride the generic frontier kernel
    fn2 = wgl.make_best_check_fn("cas-register", 64, 8, 64, 9, n_values=500)
    assert fn2 is wgl.make_check_fn("cas-register", 64, 8, 64, 9)


def test_differential_oracle_and_frontier():
    """Oracle, frontier kernel, and dense kernel must agree verdict-for-
    verdict on a mixed corpus (valid + corrupted + crashy)."""
    rng = random.Random(777)
    hists = (
        [_gen(rng, n_procs=4, n_ops=25) for _ in range(12)]
        + [_gen(rng, n_procs=4, n_ops=25, corrupt=True) for _ in range(12)]
        + [_gen(rng, n_procs=5, n_ops=18, crash_p=0.35) for _ in range(8)]
    )
    model = m.cas_register(0)
    oracle = [
        linear.analysis(model, h0, pure_fs=("read",))["valid?"] for h0 in hists
    ]
    d = _dense_verdicts(model, hists, ("read",))
    assert d == oracle
    # check_batch dispatch lands on the dense kernel and matches too
    outs = wgl.check_batch(model, hists)
    assert [o["valid?"] for o in outs] == oracle
    assert False in oracle and True in oracle  # corpus exercises both


def test_differential_register():
    rng = random.Random(4242)
    hists = [
        _gen(rng, n_procs=4, n_ops=20, corrupt=bool(i % 3 == 0), op_weights=(2, 2, 0))
        for i in range(20)
    ]
    model = m.register(0)
    oracle = [
        linear.analysis(model, h0, pure_fs=("read",))["valid?"] for h0 in hists
    ]
    assert _dense_verdicts(model, hists, ("read",)) == oracle


def _mutex_history(rng, n_procs=3, n_ops=20, corrupt=False):
    """Random acquire/release interleavings; valid by construction when
    corrupt=False (completions happen only when legal)."""
    held = None
    hist = []
    pending = {}
    idle = list(range(n_procs))
    wants = {p: "acquire" for p in range(n_procs)}
    done = 0
    while done < n_ops or pending:
        if idle and done < n_ops and (not pending or rng.random() < 0.5):
            p = rng.choice(idle)
            idle.remove(p)
            f = wants[p]
            hist.append(invoke_op(p, f))
            pending[p] = f
            done += 1
        elif pending:
            # complete a legal one if possible, else any (as a crash)
            legal = [
                p
                for p, f in pending.items()
                if (f == "acquire" and held is None)
                or (f == "release" and held == p)
            ]
            if legal:
                p = rng.choice(legal)
                f = pending.pop(p)
                held = p if f == "acquire" else None
                hist.append(ok_op(p, f))
                wants[p] = "release" if f == "acquire" else "acquire"
                idle.append(p)
            else:
                p = rng.choice(list(pending.keys()))
                f = pending.pop(p)
                hist.append(info_op(p, f))
        else:
            break
    out = History(hist)
    if corrupt:
        # double-grant: a second acquire completes while the lock is held
        out = History(
            [
                invoke_op(0, "acquire"),
                ok_op(0, "acquire"),
                invoke_op(1, "acquire"),
                ok_op(1, "acquire"),
            ]
        )
    for i, op in enumerate(out):
        op.index = i
        op.time = i
    return out


def test_differential_mutex():
    rng = random.Random(99)
    hists = [_mutex_history(rng, corrupt=bool(i % 4 == 0)) for i in range(16)]
    model = m.mutex()
    oracle = [linear.analysis(model, h0)["valid?"] for h0 in hists]
    assert _dense_verdicts(model, hists, ()) == oracle
    assert False in oracle and True in oracle


def test_dense_wide_concurrency():
    """C > 5 exercises the cross-word union/drop gathers (crashed ops
    retire their process and accumulate open slots via replace_crashed,
    mirroring interpreter process retirement)."""
    rng = random.Random(31337)
    hists = [
        _gen(
            rng,
            n_procs=9,
            n_ops=40,
            crash_p=0.1,
            corrupt=bool(i % 2),
            replace_crashed=True,
        )
        for i in range(10)
    ]
    model = m.cas_register(0)
    batch = encode.batch_encode(hists, model)
    assert batch.cand_slot.shape[2] > 5  # must actually cross words
    oracle = [
        linear.analysis(model, h0, pure_fs=("read",))["valid?"] for h0 in hists
    ]
    assert _dense_verdicts(model, hists, ("read",)) == oracle


def test_failed_event_index_matches_frontier_kernel():
    model = m.register(0)
    bad = h(
        invoke_op(0, "write", 1),
        ok_op(0, "write", 1),
        invoke_op(1, "read"),
        ok_op(1, "read", 7),
    )
    out = wgl.check_batch(model, [bad])[0]
    assert out["valid?"] is False
    assert out["engine"] == "tpu"
    assert out["failed-event"] == 1  # second ok event kills the frontier


# ---------------------------------------------------------------------------
# multi-register composite-state dense kernel
# ---------------------------------------------------------------------------


def test_mr_dense_applicability():
    from jepsen_tpu.ops import dense

    assert dense.applicable("multi-register", 8, (5, 2))       # 25 states
    assert dense.applicable("multi-register", 8, (3, 4))       # 81 = V^4
    assert not dense.applicable("multi-register", 8, (6, 3))   # 216 > cap
    assert not dense.applicable("multi-register", 16, (2, 2))  # C past cap
    assert not dense.applicable("multi-register", 8, 25)       # needs pair


def test_mr_dense_differential_two_keys():
    """K=2 composite automaton vs the CPU oracle over the fuzz corpus:
    the batch must ride kernel=dense and agree everywhere."""
    import random

    from jepsen_tpu import models as m
    from jepsen_tpu.checker import linear
    from jepsen_tpu.ops import wgl
    from jepsen_tpu.synth import generate_mr_history

    rng = random.Random(777)
    model = m.multi_register({k: 0 for k in range(2)})
    hists = [
        generate_mr_history(rng, n_keys=2, n_values=3, corrupt=(i % 3 == 0))
        for i in range(30)
    ]
    oracle = [linear.analysis(model, h)["valid?"] for h in hists]
    outs = wgl.check_batch(model, hists)
    stats = wgl.batch_stats(outs)
    assert stats["kernels"] == {"dense": 30}, stats
    assert [o["valid?"] for o in outs] == oracle
    assert True in oracle and False in oracle


def test_mr_dense_v4_four_keys():
    """The V^4 shape: four registers with a tiny per-register domain
    run dense (81 composite states at Vr=3)."""
    import random

    from jepsen_tpu import models as m
    from jepsen_tpu.checker import linear
    from jepsen_tpu.ops import wgl
    from jepsen_tpu.synth import generate_mr_history

    rng = random.Random(4100)
    model = m.multi_register({k: 0 for k in range(4)})
    # valid-only, single-value pool: corrupt/extra values widen the
    # per-register domain past the composite cap (invalid coverage
    # lives in the two-key test); Vr = 3 → 81 composite states
    hists = [
        generate_mr_history(rng, n_keys=4, n_values=1, n_ops=30)
        for i in range(20)
    ]
    oracle = [linear.analysis(model, h)["valid?"] for h in hists]
    outs = wgl.check_batch(model, hists)
    stats = wgl.batch_stats(outs)
    assert stats["kernels"] == {"dense": len(hists)}, stats
    assert [o["valid?"] for o in outs] == oracle


def test_mr_dense_golden_cross_register():
    """Writes must not bleed across registers in the composite map."""
    from jepsen_tpu import models as m
    from jepsen_tpu.history import History, invoke_op, ok_op
    from jepsen_tpu.ops import wgl

    def h(*ops):
        hist = History(ops)
        for i, op in enumerate(hist):
            op.index = i
            op.time = i
        return hist

    model = m.multi_register({0: 0, 1: 0})
    good = h(
        invoke_op(0, "txn", [("w", 0, 5)]),
        ok_op(0, "txn", [("w", 0, 5)]),
        invoke_op(0, "txn", [("r", 1, None)]),
        ok_op(0, "txn", [("r", 1, 0)]),
        invoke_op(0, "txn", [("r", 0, None)]),
        ok_op(0, "txn", [("r", 0, 5)]),
    )
    bad = h(
        invoke_op(0, "txn", [("w", 0, 5)]),
        ok_op(0, "txn", [("w", 0, 5)]),
        invoke_op(0, "txn", [("r", 1, None)]),
        ok_op(0, "txn", [("r", 1, 5)]),  # wrong register
    )
    out_good = wgl.check_batch(model, [good])[0]
    out_bad = wgl.check_batch(model, [bad])[0]
    assert out_good["kernel"] == "dense", out_good
    assert out_good["valid?"] is True
    assert out_bad["valid?"] is False


@pytest.mark.parametrize("union", ["unroll", "matmul"])
def test_union_mode_matches_gather(monkeypatch, union):
    """The unrolled static-shuffle and one-hot-matmul subset maps
    (JEPSEN_TPU_DENSE_UNION=unroll/matmul) must produce identical
    verdicts and failure indices to the default take_along_axis path
    on a corrupted mixed corpus — an on-chip A/B of the lowerings is
    only meaningful if they are bit-equivalent."""
    import random

    from jepsen_tpu import models as m
    from jepsen_tpu import synth
    from jepsen_tpu.ops import dense, encode

    rng = random.Random(45109)
    hists = [
        synth.generate_history(
            rng, n_procs=8, n_ops=120, crash_p=0.01, corrupt=(i % 3 == 0)
        )
        for i in range(12)
    ]
    batch = encode.batch_encode(hists, m.cas_register(0), slot_cap=8)
    E = batch.ev_slot.shape[1]
    C = batch.cand_slot.shape[2]
    V = encode.round_up(
        int(max(batch.cand_a.max(), batch.cand_b.max(),
                batch.init_state.max())) + 1, 4)
    args = (batch.init_state, batch.ev_slot, batch.cand_slot,
            batch.cand_f, batch.cand_a, batch.cand_b)

    monkeypatch.setenv("JEPSEN_TPU_DENSE_UNION", "gather")
    ok_g, fail_g, _ = dense.make_dense_fn("cas-register", E, C, V)(*args)
    monkeypatch.setenv("JEPSEN_TPU_DENSE_UNION", union)
    ok_u, fail_u, _ = dense.make_dense_fn("cas-register", E, C, V)(*args)
    import numpy as np

    assert (np.asarray(ok_g) == np.asarray(ok_u)).all()
    assert (np.asarray(fail_g) == np.asarray(fail_u)).all()
    assert not np.asarray(ok_g).all()  # the corpus really has invalids


@pytest.mark.parametrize("union", ["unroll", "matmul"])
def test_queue_union_mode_matches_gather(monkeypatch, union):
    """The unroll and matmul lowerings must also be bit-equivalent on
    the queue kernel (its own closure/completion use the same subset
    maps)."""
    import random

    import numpy as np

    from jepsen_tpu import models as m
    from jepsen_tpu.ops import dense, encode

    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_models import _gen_queue_history  # noqa: E402

    rng = random.Random(45110)
    hists = [_gen_queue_history(rng, n_procs=6, n_ops=24) for _ in range(8)]
    batch = encode.batch_encode(hists, m.unordered_queue(), slot_cap=6)
    E = batch.ev_slot.shape[1]
    C = batch.cand_slot.shape[2]
    args = (batch.init_state, batch.ev_slot, batch.cand_slot,
            batch.cand_f, batch.cand_a, batch.cand_b)
    monkeypatch.setenv("JEPSEN_TPU_DENSE_UNION", "gather")
    ok_g, fail_g, _ = dense.make_dense_fn("unordered-queue", E, C, 0)(*args)
    monkeypatch.setenv("JEPSEN_TPU_DENSE_UNION", union)
    ok_u, fail_u, _ = dense.make_dense_fn("unordered-queue", E, C, 0)(*args)
    assert (np.asarray(ok_g) == np.asarray(ok_u)).all()
    assert (np.asarray(fail_g) == np.asarray(fail_u)).all()


def test_unknown_union_mode_rejected():
    from jepsen_tpu.ops import dense

    with pytest.raises(ValueError):
        dense.build_dense("cas-register", 8, 4, 8, union="zip")


def test_union_mode_resolves_env_then_default(monkeypatch):
    from jepsen_tpu.ops import dense

    # every consumer resolves the lowering through dense._union_mode:
    # the env override wins, and without it the one DEFAULT_UNION holds
    monkeypatch.setenv("JEPSEN_TPU_DENSE_UNION", "gather")
    assert dense._union_mode() == "gather"
    monkeypatch.delenv("JEPSEN_TPU_DENSE_UNION")
    assert dense._union_mode() == dense.DEFAULT_UNION
