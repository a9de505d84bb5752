"""The plain reference: Elle's list-append analysis of a Jepsen
``cycle.append`` test, written from the published definitions (Adya's
phenomena as Elle infers them, Kingsbury & Alvaro, "Elle: Inferring
Isolation Anomalies from Experimental Observations", VLDB 2020).
Nothing here imports the program.

A transaction is an invocation paired with its process's next
completion.  Reads observe whole lists, so a key's version order is
its longest read, and every read of the key must be a prefix of it.
The dependency graph has a vertex per ``ok`` transaction and these
edges, each a set of relations:

- ``ww``: the writer of an element to the writer of the next element
  of the key's version order;
- ``wr``: the writer of the last element a read observed (less the
  reader's own appends) to the reader;
- ``rw``: a reader to the writer of the element after the last one it
  observed;
- ``process``: a process's ok transaction to its next ok one;
- ``realtime``: T1 to T2 where T1 completed before T2 was invoked,
  reduced to the pairs with no third transaction between them (T1
  completed before T3 was invoked, T3 completed before T2 was).

An anomaly is a cycle of an SCC of the whole graph by its edge
profile: G0 all ``ww``; G1c ``ww``/``wr``; G-single exactly one
``rw``, the rest ``ww``/``wr``; G-nonadjacent a simple cycle with
``rw`` edges of which no two are adjacent, cyclically; G2-item any
cycle of ``ww``/``wr``/``rw``.  The ``-process`` and ``-realtime``
variants let ``process`` and ``realtime`` edges into the rest, and
name the cycle by whether it needs a ``realtime`` edge.  Where Elle's
reporting and the checker under test differ, this reference follows
the checker, and says so where it does.

``known`` gives the verdicts the generator knows; ``search`` decides
labels by the analysis above.  ``search(..., realtime=False)`` drops
the realtime edges: the control, which checks sequential consistency
where strict serializability is asked for.
"""

from __future__ import annotations

import bisect

WW, WR, RW, PROCESS, REALTIME = "ww", "wr", "rw", "process", "realtime"
#: the anomalies that are not cycles; the generator's stores make none
NON_CYCLE = ("internal", "G1a", "G1b", "dirty-update",
             "duplicate-elements", "incompatible-order")
PLAIN = ("G0", "G1c", "G-single", "G-nonadjacent", "G2-item")
#: a bounded search's give-up markers: an exact search never gives up
INDETERMINATE = ("G-nonadjacent-indeterminate",
                 "G-nonadjacent-process-indeterminate",
                 "G-nonadjacent-realtime-indeterminate")
#: steps of the simple-cycle search before it fails loudly
SIMPLE_CYCLE_STEPS = 20_000_000


class Txn:
    __slots__ = ("idx", "inv", "comp", "type", "process", "mops")

    def __init__(self, idx, inv, process, mops):
        self.idx = idx
        self.inv = inv
        self.comp = None
        self.type = None
        self.process = process
        self.mops = mops


def transactions(events):
    """Invocations paired with their completions, in invocation order;
    an ok transaction's micro-ops are its completion's."""
    txns = []
    open_ = {}
    for typ, p, value, ts in events:
        if typ == "invoke":
            t = Txn(len(txns), ts, p, value)
            txns.append(t)
            open_[p] = t
            continue
        t = open_.pop(p, None)
        if t is not None:
            t.comp, t.type = ts, typ
            if typ == "ok":
                t.mops = value
    return txns


def _reads(t):
    """(key, observed list) of each of ``t``'s reads; an unobserved
    read (None) is an empty list to the edges, and no version."""
    return [(k, v) for f, k, v in t.mops if f == "r"]


def non_cycle(txns):
    """{name: bool} for each anomaly of NON_CYCLE, and each key's
    version order."""
    ok = [t for t in txns if t.type == "ok"]
    failed = {(k, v) for t in txns if t.type == "fail"
              for f, k, v in t.mops if f == "append"}
    appends = {}                                  # txn idx -> key -> [el]
    for t in txns:
        if t.type != "fail":
            for f, k, v in t.mops:
                if f == "append":
                    appends.setdefault(t.idx, {}).setdefault(k, []).append(v)
    last_of = {}                                  # (k, el) -> is last
    for per_key in appends.values():
        for k, els in per_key.items():
            for i, el in enumerate(els):
                last_of[(k, el)] = (i == len(els) - 1)
    found = dict.fromkeys(NON_CYCLE, False)
    order = {}
    for t in ok:
        state = {}
        mine = appends.get(t.idx, {})
        for f, k, v in t.mops:
            if f == "append":
                kind, lst = state.get(k, ("suffix", []))
                state[k] = (kind, lst + [v])
                continue
            if v is None:
                continue
            v = list(v)
            if k in state:
                kind, lst = state[k]
                if (v != lst if kind == "exact"
                        else v[len(v) - len(lst):] != lst or len(v) < len(lst)):
                    found["internal"] = True
            state[k] = ("exact", v)
            if any((k, el) in failed for el in v):
                found["G1a"] = True
            if v and not last_of.get((k, v[-1]), True) \
                    and v[-1] not in mine.get(k, ()):
                found["G1b"] = True
            if len(set(v)) != len(v):
                found["duplicate-elements"] = True
            # the version order: the first of the longest reads, as the
            # checker takes it (Elle merges compatible reads)
            if k not in order or len(v) > len(order[k]):
                order[k] = v
    for t in ok:
        for k, v in _reads(t):
            if v is not None and list(v) != order[k][:len(v)]:
                found["incompatible-order"] = True
    written = {(k, el) for per_key in appends.values()
               for k, els in per_key.items() for el in els}
    # the checker's dirty update: an element of a version order that
    # only a failed transaction appended (Elle asks for a committed
    # write after it)
    found["dirty-update"] = any(
        (k, el) not in written and (k, el) in failed
        for k, els in order.items() for el in els)
    return found, order, appends


def dependency_graph(txns, order, appends, realtime=True):
    """{a: {b: set of relations}} over the ok transactions' indices."""
    ok = [t for t in txns if t.type == "ok"]
    is_ok = {t.idx for t in ok}
    writer = {(k, el): i for i, per_key in appends.items()
              for k, els in per_key.items() for el in els}
    out = {t.idx: {} for t in ok}

    def edge(a, b, rel):
        if a != b and a in is_ok and b in is_ok:
            out[a].setdefault(b, set()).add(rel)

    for k, els in order.items():
        for a, b in zip(els, els[1:]):
            if (k, a) in writer and (k, b) in writer:
                edge(writer[k, a], writer[k, b], WW)
    for t in ok:
        mine = appends.get(t.idx, {})
        for k, v in _reads(t):
            v = list(v or ())
            own = mine.get(k, ())
            while v and v[-1] in own:
                v.pop()
            if v and (k, v[-1]) in writer:
                edge(writer[k, v[-1]], t.idx, WR)
            els = order.get(k, [])
            if v == els[:len(v)] and len(v) < len(els) \
                    and (k, els[len(v)]) in writer:
                edge(t.idx, writer[k, els[len(v)]], RW)
    last = {}
    for t in ok:
        if t.process in last:
            edge(last[t.process], t.idx, PROCESS)
        last[t.process] = t.idx
    if realtime:
        # T1 -> T2 iff X < c1 < i2, X the latest invocation among the
        # transactions completed before i2
        by_comp = sorted(ok, key=lambda t: t.comp)
        comps = [t.comp for t in by_comp]
        latest_inv = []
        m = None
        for t in by_comp:
            m = t.inv if m is None else max(m, t.inv)
            latest_inv.append(m)
        for t2 in ok:
            j = bisect.bisect_left(comps, t2.inv)
            if j == 0:
                continue
            x = latest_inv[j - 1]
            for t1 in by_comp[bisect.bisect_right(comps, x):j]:
                edge(t1.idx, t2.idx, REALTIME)
    return out


def sccs(vertices, succ):
    """Tarjan's strongly connected components of the graph on
    ``vertices`` with successor lists ``succ(v)``, in reverse
    topological order (sinks first)."""
    index, low, on, stack, comps = {}, {}, set(), [], []
    n = 0
    for root in vertices:
        if root in index:
            continue
        index[root] = low[root] = n
        n += 1
        stack.append(root)
        on.add(root)
        work = [(root, iter(succ(root)))]
        while work:
            v, it = work[-1]
            for w in it:
                if w not in index:
                    index[w] = low[w] = n
                    n += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(succ(w))))
                    break
                if w in on:
                    low[v] = min(low[v], index[w])
            else:
                work.pop()
                if work:
                    u = work[-1][0]
                    low[u] = min(low[u], low[v])
                if low[v] == index[v]:
                    comp = []
                    while True:
                        w = stack.pop()
                        on.discard(w)
                        comp.append(w)
                        if w == v:
                            break
                    comps.append(comp)
    return comps


class Scc:
    """One nontrivial SCC of the whole graph, and the cycle questions
    the anomaly profiles ask of it."""

    def __init__(self, members, out):
        self.members = set(members)
        self.out = {a: {b: r for b, r in out[a].items() if b in self.members}
                    for a in members}

    def _succ(self, keep):
        return {a: [b for b, r in nbrs.items() if keep(r)]
                for a, nbrs in self.out.items()}

    def cyclic(self, keep):
        """A cycle of edges whose relations satisfy ``keep``?"""
        s = self._succ(keep)
        return any(len(c) > 1 for c in sccs(s, s.__getitem__))

    def reach(self, keep):
        """Component of each vertex under ``keep``'s edges, and each
        component's reachable components as a bit set."""
        s = self._succ(keep)
        comps = sccs(s, s.__getitem__)
        comp = {v: i for i, c in enumerate(comps) for v in c}
        bits = []
        for i, c in enumerate(comps):       # sinks first
            b = 1 << i
            for v in c:
                for w in s[v]:
                    b |= bits[comp[w]] if comp[w] != i else 0
            bits.append(b)
        return comp, bits

    def one_want(self, want, rest):
        """A cycle with exactly one ``want`` edge, the rest ``rest``
        edges: a want edge u -> v with u reachable from v over rest
        edges."""
        comp, bits = self.reach(rest)
        return any(want(r) and bits[comp[b]] >> comp[a] & 1
                   for a, nbrs in self.out.items() for b, r in nbrs.items())

    def nonadjacent(self, want, rest):
        """A simple cycle of ``want`` and ``rest`` edges with at least
        one ``want`` edge and no two of them adjacent, cyclically."""
        # a closed walk of that kind first: (v, 1) after a want edge,
        # (v, 0) after a rest one; want edges leave state 0 only
        lifted = {}
        for a, nbrs in self.out.items():
            for s in (0, 1):
                lifted[(a, s)] = [(b, 0) for b, r in nbrs.items()
                                  if rest(r)]
            lifted[(a, 0)] += [(b, 1) for b, r in nbrs.items() if want(r)]
        comps = sccs(lifted, lifted.__getitem__)
        comp = {v: i for i, c in enumerate(comps) for v in c}
        bits = []
        for i, c in enumerate(comps):
            b = 1 << i
            for v in c:
                for w in lifted[v]:
                    b |= bits[comp[w]] if comp[w] != i else 0
            bits.append(b)
        if not any(want(r) and bits[comp[(b, 1)]] >> comp[(a, 0)] & 1
                   for a, nbrs in self.out.items() for b, r in nbrs.items()):
            return False
        return self._simple_nonadjacent(want, rest)

    def _simple_nonadjacent(self, want, rest):
        """Depth-first over simple paths: each cycle is tried from its
        least vertex in a fixed order, through vertices above it that
        can reach it, each edge as want and as rest where it can be
        either."""
        order = sorted(self.members)
        rank = {v: i for i, v in enumerate(order)}
        pred = {v: [] for v in order}
        for a, nbrs in self.out.items():
            for b, r in nbrs.items():
                if want(r) or rest(r):
                    pred[b].append(a)

        def options(v):
            for b, r in self.out[v].items():
                if want(r):
                    yield b, True
                if rest(r):
                    yield b, False

        steps = 0
        for s in order:
            back = {s}
            todo = [s]
            while todo:
                for a in pred[todo.pop()]:
                    if rank[a] > rank[s] and a not in back:
                        back.add(a)
                        todo.append(a)
            if len(back) < 2:
                continue
            # (vertex, first edge want, last edge want, wants, options)
            stack = [(s, None, False, 0, options(s))]
            on_path = {s}
            while stack:
                steps += 1
                if steps > SIMPLE_CYCLE_STEPS:
                    raise RuntimeError("simple-cycle search out of steps")
                v, first, last, n_want, it = stack[-1]
                nxt = next(it, None)
                if nxt is None:
                    stack.pop()
                    on_path.discard(v)
                    continue
                b, as_want = nxt
                if as_want and last:
                    continue
                head = as_want if first is None else first
                if b == s:
                    # the closing edge is adjacent to the first one
                    if n_want + as_want and not (as_want and head):
                        return True
                    continue
                if b in on_path or b not in back:
                    continue
                on_path.add(b)
                stack.append((b, head, as_want, n_want + as_want,
                              options(b)))
        return False


def ladder(scc):
    """The anomaly of one SCC, most severe first, as the checker under
    test reports: one per SCC (Elle reports every profile it finds).
    A suffixed profile is named ``-process`` where a cycle of it needs
    no realtime edge, else ``-realtime``: the checker names the cycle
    it finds by its edges, and where both kinds exist it may find
    either, which the generator's stores never allow (every process
    edge is also a realtime edge)."""
    def has(*rels):
        rels = set(rels)
        return lambda r: bool(r & rels)

    rw = has(RW)
    if scc.cyclic(has(WW)):
        return "G0"
    if scc.cyclic(has(WW, WR)):
        return "G1c"
    if scc.one_want(rw, has(WW, WR)):
        return "G-single"
    if scc.nonadjacent(rw, has(WW, WR)):
        return "G-nonadjacent"
    if scc.cyclic(has(WW, WR, RW)):
        return "G2-item"

    def fires(name, realtime):
        """A cycle of the suffixed profile, with realtime edges or
        without them."""
        def keep(*rels):
            k = has(*rels, PROCESS, REALTIME)
            return k if realtime else (
                lambda r: REALTIME not in r and k(r))
        want = rw if realtime else (
            lambda r: RW in r and REALTIME not in r)
        if name == "G0":
            return scc.cyclic(keep(WW))
        if name == "G1c":
            return scc.cyclic(keep(WW, WR))
        if name == "G-single":
            return scc.one_want(want, keep(WW, WR))
        if name == "G-nonadjacent":
            return scc.nonadjacent(want, keep(WW, WR))
        return scc.cyclic(keep(WW, WR, RW))

    for name in PLAIN:
        if fires(name, True):
            return name + ("-process" if fires(name, False) else "-realtime")
    raise AssertionError("a strongly connected component with no cycle")


def analyse(events, realtime=True):
    """{label: verdict} for every anomaly name and ``valid?``."""
    txns = transactions(events)
    found, order, appends = non_cycle(txns)
    out = dependency_graph(txns, order, appends, realtime)
    for name in PLAIN:
        found[name] = False
        found[name + "-process"] = False
        found[name + "-realtime"] = False
    for name in INDETERMINATE:
        found[name] = False
    for members in sccs(out, lambda v: out[v]):
        if len(members) > 1:
            found[ladder(Scc(members, out))] = True
    found["valid?"] = not any(found.values())
    return found


#: what a lagging replica's reads can make: a stale prefix of whole
#: transactions' effects is a cycle's rw edge, and a stale read after
#: the transaction's own append is no prefix of the version order
STALE = ("incompatible-order",)


def known(cfg, traffic, item):
    """A test from a strictly serializable store is valid with no
    anomaly.  In a faulty store's test the cycles and STALE are left
    to the search; the rest cannot occur, and no exact search gives
    up."""
    faulty, _events = item
    out = {name: False for name in cfg["labels"]}
    out["valid?"] = True
    if faulty:
        for name in out:
            if name not in INDETERMINATE and (name not in NON_CYCLE
                                              or name in STALE):
                out[name] = None
    return out


def search(cfg, traffic, item, labels, realtime=True):
    """The labels asked for, by :func:`analyse`; ``realtime=False`` is
    the control's."""
    got = analyse(item[1], realtime)
    return {label: got[label] for label in labels}
