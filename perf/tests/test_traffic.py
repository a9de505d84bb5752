"""The generator: a seed fixes the pool; the pool has the shapes its
traffic file states; each cell's pool is the one it has always been."""

import hashlib
import json
import os

import harness
import pytest

gen = harness.cell("cas1k_batch").module("generator")
PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(PERF, kind, name + ".json")) as f:
        return json.load(f)


def small_single():
    cfg = dict(load("configs", "cas_register_1k"), histories_per_request=16,
               ops_per_history=100)
    # crashed processes replaced, as the deferred crashy cells would ask
    return cfg, dict(load("traffic", "batch1024"), crash_p=0.004,
                     replace_crashed=True)


def small_keyed():
    cfg = load("configs", "keyed_register")
    tr = dict(load("traffic", "keyed_ladder"), pool_tests=8,
              keys_per_test=[5, 40])
    return cfg, tr


def test_same_seed_same_pool():
    for cfg, tr in (small_single(), small_keyed()):
        a = gen.make_pool(cfg, tr, 2**31 + 77)
        b = gen.make_pool(cfg, tr, 2**31 + 77)
        c = gen.make_pool(cfg, tr, 2**31 + 78)
        assert a == b
        assert a != c


def test_single_key_shape():
    cfg, tr = small_single()
    pool = gen.make_pool(cfg, tr, 5)
    assert len(pool) == tr["pool_requests"]
    for req in pool:
        assert len(req) == cfg["histories_per_request"]
        for h in req:
            assert gen.client_ops(h) == cfg["ops_per_history"]


def test_keyed_ladder_and_limits():
    cfg, tr = small_keyed()
    pool = gen.make_pool(cfg, tr, 9)
    sizes = [len(req[0]) for req in pool]
    assert sizes == gen.ladder(5, 40, 8) == sorted(sizes)
    for (keys,) in pool:
        for _k, h in keys:
            procs = {p for _t, p, _f, _v in h}
            assert len(procs) <= cfg["process_limit"]
            assert gen.client_ops(h) <= cfg["per_key_limit"]


def test_ladder_is_log_uniform_quantiles():
    lad = gen.ladder(20, 500, 128)
    assert lad[0] == 20 and lad[-1] == 494
    assert lad == sorted(lad)


def test_copy_matches_synth():
    """single_history draws the same stream as synth.generate_history."""
    import random
    import sys

    sys.path.append(os.path.dirname(PERF))
    from jepsen_tpu import synth

    for seed in range(5):
        ours = gen.single_history(random.Random(seed), 4, 60, 0.1, True, 5,
                                  replace_crashed=True)
        theirs = synth.generate_history(random.Random(seed), 4, 60, 0.1, True,
                                        5, replace_crashed=True)
        assert ours == [(o.type, o.process, o.f, o.value) for o in theirs]


def pool_hash(pool):
    h = hashlib.sha256()
    for req in pool:
        h.update(b"R")
        for item in req:
            h.update(repr(item).encode())
            h.update(b"\n")
    return h.hexdigest()


def cell_pool(workload, seed, shrunk):
    """The pool a run of ``workload`` makes from ``seed``."""
    cell = harness.cell(workload, shrunk)
    return cell.module("generator").make_pool(cell.config, cell.traffic,
                                              seed)


#: the pools' hashes as the CAS-register generator made them while it
#: was the harness's only one, at the cells' test sizes and at full size
BATCH_SHRUNK = {
    2**31 + 5: "4d2b94cd48a4fb8d84e088f80e49f30345325e9714627f0839f5e1f9ded8e7fe",
    2**31 + 11: "d1ee7960c83a33f76a654becbb1cf938e155a0e07d6a8c1b20f96ca6dae2c9e9",
    3000000019: "d3a3256d6ae4f88e3a6ed4f6f06f0d898e8b5e4d0a30aafc13cc688f1bca0ce4",
}
BATCH_FULL = {
    2400002611: "c07315733e194deef464b5be1a3af10b5dd5164d82fb7f59adceb98ab7e6db89",
}
PINNED = {
    "cas1k_batch": (BATCH_SHRUNK, BATCH_FULL),
    # the same traffic as cas1k_batch under another name
    "cas1k_batch_4chip": (BATCH_SHRUNK, BATCH_FULL),
    "keyed_test": ({
        2**31 + 5: "18a0d5fa40b4bd1233130bb8497faa5fb90deed8ecc9ebe4fa79463bf0dcb4c9",
        2**31 + 11: "acb1bf4ce22266b550249e0672a109b7d4d2e669088177762dbbcd68c050b167",
        3000000019: "99eefd2a72396950c4c65f9e6e68b6954e1553f24f585d4ddc24eda151999fbb",
    }, {
        2400002611: "ab56796b25b9ba4e77e5e554928bc064a679012a8bac34d9e462500d4abbf414",
    }),
}


@pytest.mark.parametrize("shrunk", [True, False], ids=["test_size", "full"])
@pytest.mark.parametrize("workload", sorted(PINNED))
def test_pool_is_pinned(workload, shrunk):
    pinned = PINNED[workload][0 if shrunk else 1]
    for seed, want in pinned.items():
        assert pool_hash(cell_pool(workload, seed, shrunk)) == want, seed
