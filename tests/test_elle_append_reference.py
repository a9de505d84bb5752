"""Elle list-append against a plain reference, and the SCC-split screen.

The reference (``perf/reference/elle_append.py``, loaded by path; it
imports nothing of the program) decides every anomaly label of seeded
histories from ``perf/generators/elle_append.py``: from a strictly
serializable store, and with stale reads, crashes and aborts.  The
checker has to give the same labels with its cycle screens on the CPU
and on the device, and every witness cycle it reports has to be a
cycle of the reference's graph with the profile its name says.
``classify_graphs`` screens each SCC of a graph's union on its own:
past the vertex cap an SCC stays on the CPU, a single graph with
enough SCCs reaches the device, an acyclic union needs no screen, and
the results never differ from the CPU route's."""

import importlib.util
import json
import os
import random

import pytest

from jepsen_tpu import obs
from jepsen_tpu.elle import cycles as elle_cycles
from jepsen_tpu.elle import list_append
from jepsen_tpu.elle.graph import Graph
from jepsen_tpu.history import History, Op
from jepsen_tpu.workloads.cycle import append

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")


def _load(kind, name):
    path = os.path.join(PERF, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gen = _load("generators", "elle_append")
ref = _load("reference", "elle_append")
with open(os.path.join(PERF, "configs", "elle_list_append.json")) as f:
    CFG = json.load(f)

#: each kind of history: whether the store lags, and what it changes
KINDS = {
    "valid": (False, {}),
    "stale": (True, {"stale_read_p": 0.3}),
    "crashed": (True, {"crash_p": 0.2, "stale_read_p": 0.05}),
    "aborted": (True, {"abort_p": 0.3, "stale_read_p": 0.05}),
}


def events_of(kind, seed, n=48, **changes):
    faulty, over = KINDS[kind]
    cfg = dict(CFG, **dict(over, **changes))
    return gen.simulate(random.Random(seed), n, cfg, faulty)


def history(events):
    return History([Op(t, p, "txn", v, time=ts)
                    for t, p, v, ts in events]).index_ops()


def check(events, route):
    checker = append.checker({"consistency-models": ["strict-serializable"],
                              "screen-route": route})
    return checker.check({}, history(events), {})


def labels_of(result):
    found = set(result.get("anomaly-types", ())) | set(
        result.get("also-anomaly-types", ()))
    out = {name: name in found for name in CFG["labels"]}
    out["valid?"] = result["valid?"]
    return out


def _index(vertex):
    return int(vertex[1:vertex.index("(")])


def _usages(rels, want, rest):
    """The ways one step can be used: as the profile's want edge, as
    a rest edge."""
    out = []
    if rels & want:
        out.append(True)
    if rels & rest:
        out.append(False)
    return out


def _assignable(steps, want, rest, exactly_one=False, nonadjacent=False):
    """Some usage of each step gives the profile: at least one want
    usage, exactly one or no two cyclically adjacent."""
    options = [_usages(r, want, rest) for r in steps]
    if not all(options):
        return False
    n = len(steps)

    def walk(i, first, last, wants):
        if i == n:
            if nonadjacent and last and first:
                return False
            return wants == 1 if exactly_one else wants >= 1
        for w in options[i]:
            if nonadjacent and w and last and i > 0:
                continue
            if exactly_one and wants + w > 1:
                continue
            if walk(i + 1, w if i == 0 else first, w, wants + w):
                return True
        return False

    return walk(0, False, False, 0)


PLAIN_REST = {"G0": {"ww"}, "G1c": {"ww", "wr"}, "G-single": {"ww", "wr"},
              "G-nonadjacent": {"ww", "wr"}, "G2-item": {"ww", "wr", "rw"}}


def assert_witness(name, item, graph):
    """``item`` is a cycle of the reference's ``graph`` whose edges
    carry exactly the relations it names, with ``name``'s profile."""
    cyc = [_index(v) for v in item["cycle"]]
    assert cyc[0] == cyc[-1] and len(cyc) > 2, item
    steps = []
    for a, b, step in zip(cyc, cyc[1:], item["steps"]):
        assert set(step["rels"]) == graph[a].get(b), (name, a, b, step)
        steps.append(set(step["rels"]))
    base, suffix = name, ""
    for s in ("-process", "-realtime"):
        if name.endswith(s):
            base, suffix = name[:-len(s)], s
    used = set().union(*steps)
    if suffix == "-realtime":
        assert "realtime" in used, item
    elif suffix == "-process":
        assert "process" in used and "realtime" not in used, item
    rest = set(PLAIN_REST[base]) | ({"process", "realtime"} if suffix
                                     else set())
    if base in ("G0", "G1c", "G2-item"):
        assert all(s & rest for s in steps), item
    elif base == "G-single":
        assert _assignable(steps, {"rw"}, rest, exactly_one=True), item
    else:
        assert len(set(cyc[:-1])) == len(cyc) - 1, item
        assert _assignable(steps, {"rw"}, rest, nonadjacent=True), item


@pytest.mark.parametrize("route", ["cpu", "device"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_checker_agrees_with_the_reference(kind, route):
    for seed in range(3):
        events = events_of(kind, seed)
        got = labels_of(check(events, route))
        want = ref.search(CFG, {}, (KINDS[kind][0], events),
                          list(got))
        assert got == want, (kind, seed)
        known = ref.known(CFG, {}, (KINDS[kind][0], events))
        assert all(v is None or v == want[k] for k, v in known.items())


@pytest.mark.parametrize("route", ["cpu", "device"])
def test_every_witness_is_a_reference_cycle_of_its_profile(route):
    seen = set()
    for seed in range(4):
        events = events_of("stale", seed, n=64)
        result = check(events, route)
        txns = ref.transactions(events)
        _found, order, appends = ref.non_cycle(txns)
        graph = ref.dependency_graph(txns, order, appends)
        for name, items in result.get("anomalies", {}).items():
            for item in items:
                if isinstance(item, dict) and "cycle" in item:
                    assert_witness(name, item, graph)
                    seen.add(name)
    assert seen, "the stale histories made no cycle"


def test_routes_agree_on_every_kind_byte_for_byte():
    for kind in sorted(KINDS):
        events = events_of(kind, 7)
        cpu = check(events, "cpu")
        dev = check(events, "device")
        assert json.dumps(cpu, sort_keys=True, default=repr) == json.dumps(
            dev, sort_keys=True, default=repr), kind


# ---------------------------------------------------------------------------
# the SCC split
# ---------------------------------------------------------------------------


def _graph(events):
    g, _txns, _anoms, _wanted = list_append.prepare(
        history(events), {"consistency-models": ["strict-serializable"]})
    return g


def _dump(results):
    return json.dumps(results, sort_keys=True, default=repr)


def test_scc_past_the_cap_stays_on_the_cpu_and_results_match(monkeypatch):
    g = _graph(events_of("stale", 3, n=120, stale_read_p=0.05))
    sizes = sorted(len(c) for c in
                   elle_cycles.strongly_connected_components(g))
    assert len(sizes) >= 2 and sizes[-2] < sizes[-1], sizes
    cap = sizes[-1] - 1
    assert len(g.vertices) > cap
    cpu = elle_cycles.classify_graphs([g], route="cpu")
    monkeypatch.setattr(elle_cycles, "DEVICE_SCREEN_MAX_VERTICES", cap)
    obs.enable(reset=True)
    try:
        dev = elle_cycles.classify_graphs([g], route="device")
        reg = obs.registry()
        on_cpu = reg.value("jepsen_elle_scc_vertices_total", route="cpu")
        on_dev = reg.value("jepsen_elle_scc_vertices_total", route="device")
        n_dev = reg.value("jepsen_elle_scc_total", route="device")
    finally:
        obs.enable(reset=True)
    assert _dump(dev) == _dump(cpu)
    assert on_cpu == sizes[-1]
    assert on_dev == sum(sizes) - sizes[-1] and n_dev == len(sizes) - 1


def _rings(k, size=3):
    """``k`` disjoint rw-closed rings joined by ww edges into one
    graph: ``k`` nontrivial SCCs."""
    g = Graph()
    for r in range(k):
        ring = [(r, i) for i in range(size)]
        for a, b in zip(ring, ring[1:]):
            g.add_edge(a, b, "ww")
        g.add_edge(ring[-1], ring[0], "rw")
        if r:
            g.add_edge((r - 1, 0), ring[0], "ww")
    return g


def test_one_graph_reaches_the_device_once_its_scc_batch_is_big_enough(
        monkeypatch):
    n = elle_cycles.ELLE_SCREEN_MIN_BATCH
    calls = []
    real = elle_cycles.screen_for_graphs

    def spy(graphs, executor=None, encs=None):
        calls.append(len(graphs))
        return real(graphs, executor=executor, encs=encs)

    monkeypatch.setattr(elle_cycles, "screen_for_graphs", spy)
    monkeypatch.setattr(elle_cycles, "_CLASSIFY_CHOICE", {})
    small = _rings(n - 1)
    assert elle_cycles.classify_graphs([small]) == [
        elle_cycles.classify(small)]
    assert calls == [] and elle_cycles._CLASSIFY_CHOICE == {}

    g = _rings(n)
    out = elle_cycles.classify_graphs([g])
    assert _dump(out) == _dump([elle_cycles.classify(g)])
    assert calls and all(c == n for c in calls)
    key = (elle_cycles._screen_bucket(3), elle_cycles._screen_bucket(n))
    assert elle_cycles._CLASSIFY_CHOICE.get(key) in ("cpu", "device")


def test_an_acyclic_union_needs_no_screen(monkeypatch):
    def boom(*_a, **_kw):
        raise AssertionError("screened an acyclic graph")

    monkeypatch.setattr(elle_cycles, "screen_for_graphs", boom)
    g = _graph(events_of("valid", 1, n=80))
    assert elle_cycles.strongly_connected_components(g) == []
    obs.enable(reset=True)
    try:
        assert elle_cycles.classify_graphs([g], route="device") == [{}]
        assert not any(s.name == "elle/screen"
                       for s in obs.tracer().finished())
    finally:
        obs.enable(reset=True)


def test_spans_carry_the_screened_workload():
    events = events_of("stale", 3, n=96)
    obs.enable(reset=True)
    try:
        check(events, "device")
        spans = [s.to_dict() for s in obs.tracer().finished()]
    finally:
        obs.enable(reset=True)
    names = {s["name"] for s in spans}
    assert {"elle/infer", "elle/scc", "elle/screen", "elle/search"} <= names
    (screen,) = [s for s in spans if s["name"] == "elle/screen"]
    sizes = [int(x) for x in screen["attrs"]["scc_vertices"].split(",")]
    planes = screen["attrs"]["planes"].split(",")
    walks = screen["attrs"]["walks"].split(",")
    assert len(sizes) == len(planes) == len(walks) >= 1
    assert all(n >= 2 for n in sizes)


def _dirty_update_before(txns, orders, writer_of):
    """The dirty-update pass as it was: every transaction scanned for
    every element of a version order with no ok or info writer."""
    dirty = []
    for k, order in orders.items():
        for el in order:
            if writer_of.get((k, el)) is None and [
                    t for t in txns if t.failed and any(
                        f == "append" and kk == k and vv == el
                        for f, kk, vv in list_append.mops(t))]:
                dirty.append({"key": k, "element": el})
    return dirty


def test_dirty_update_is_unchanged_by_the_index():
    """Reads of many elements whose writers failed: the indexed pass
    reports what the scan over every transaction did."""
    rng = random.Random(11)
    ops, t = [], 0
    for p in range(40):
        k = p % 4
        v = 100 + p
        ops.append(Op("invoke", p, "txn", [["append", k, v]], time=t))
        ops.append(Op("fail" if p % 3 else "ok", p, "txn",
                      [["append", k, v]], time=t + 1))
        t += 2
    for k in range(4):
        els = [100 + p for p in range(40) if p % 4 == k]
        rng.shuffle(els)
        ops.append(Op("invoke", 99, "txn", [["r", k, None]], time=t))
        ops.append(Op("ok", 99, "txn", [["r", k, els]], time=t + 1))
        t += 2
    h = History(ops).index_ops()
    g, txns, anomalies = list_append.graph_and_anomalies(h)
    orders, _inc = list_append.version_orders(txns)
    writer_of = {(k, v): tx for tx in txns if not tx.failed
                 for f, k, v in list_append.mops(tx) if f == "append"}
    before = _dirty_update_before(txns, orders, writer_of)
    assert len(before) > 20
    assert anomalies["dirty-update"] == before


#: the relation filters of the ladder's BFS rungs
RUNG_FILTERS = ({"ww"}, {"ww", "wr"}, {"ww", "wr", "rw"},
                {"ww", "process", "realtime"},
                {"ww", "wr", "rw", "process", "realtime"})


@pytest.mark.parametrize("seed", range(6))
def test_searching_from_cycle_vertices_alone_finds_the_same_witness(seed):
    """A witness BFS that starts and walks only among the vertices on
    some cycle of its rung's subgraph returns the cycle the BFS over
    the whole SCC returns, or None where it does: on random typed
    graphs, every SCC of their union, every rung's filter."""
    from jepsen_tpu.elle.graph import (find_cycle, find_cycle_with,
                                       strongly_connected_components)

    rng = random.Random(seed)
    rels = ("ww", "wr", "rw", "process", "realtime")
    for _ in range(20):
        g = Graph()
        n = rng.randrange(4, 40)
        for _e in range(rng.randrange(n, 4 * n)):
            a, b = rng.randrange(n), rng.randrange(n)
            g.add_edge(a, b, rng.choice(rels))
        for scc, h in elle_cycles.scc_parts(g):
            for keep in RUNG_FILTERS:
                sub = h.filtered(lambda r, k=keep: bool(r & k))
                on = elle_cycles._on_cycles(sub, scc)
                assert set(on) == {v for c in
                                   strongly_connected_components(sub)
                                   for v in c}
                assert find_cycle(sub, on) == find_cycle(sub, scc)
                # one rw edge, the rest from the filter: the BFS walks
                # the subgraph of the filter and rw together
                walked = h.filtered(lambda r, k=keep | {"rw"}: bool(r & k))
                args = dict(want=lambda r: "rw" in r,
                            rest=lambda r, k=keep - {"rw"}: bool(r & k),
                            want_count=1)
                assert (find_cycle_with(
                    h, elle_cycles._on_cycles(walked, scc), **args)
                        == find_cycle_with(h, scc, **args))
