"""The list-append generator: Jepsen ``cycle.append`` tests from a
configuration, a traffic file's parameters and a seed, as plain tuples.

Each test is a seeded closed-loop simulation of ``concurrency`` client
processes against an in-memory list-append store, on a simulated
clock.  A transaction's micro-ops are drawn as Elle's rotating-key
generator draws them (``jepsen_tpu.workloads.cycle.TxnGenerator``):
``key_count`` active keys, each retired for a fresh one after
``max_writes_per_key`` appends, ``min_txn_length`` to
``max_txn_length`` micro-ops, each a read with probability
``read_fraction`` and else an append of a value unique in the test.

The store runs each transaction atomically at a commit point between
its invocation and its completion (strict serializability), except
that in a faulty test (every ``1 / faulty_fraction``-th size of the
ladder) a read of a key the transaction has not touched yet is served,
with probability ``stale_read_p``, from the key's state ``lag``
seconds before the commit point, the lag log-uniform over
``stale_lag_s``: a lagging replica.  A transaction aborts with
probability ``abort_p``, refused at begin: its ``fail`` is the next
event after its invocation, and it has no effect.  It crashes with
probability ``crash_p``: it takes effect at its commit point or not,
at even odds, completes ``info``, and its process is replaced by
``process + concurrency``, as Jepsen's interpreter does.  A client
invokes its next transaction as the next event after its last one
completed, so every process edge of a test is also a realtime edge.

An item is one test: ``(faulty, events)`` with events ``(type,
process, value, time)``, ``type`` in invoke/ok/fail/info, ``value`` a
tuple of micro-ops ``("r", key, None | tuple)`` or ``("append", key,
element)``, and ``time`` in nanoseconds, strictly increasing.  Nothing
here imports the program; ``perf/entries/elle_checker.py`` turns
events into its ops.
"""

from __future__ import annotations

import bisect
import heapq
import math
import random

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"


def ladder(lo, hi, n):
    """``n`` transaction counts at the quantiles (i + 0.5) / n of the
    log-uniform distribution over [lo, hi]."""
    return [int(round(math.exp(math.log(lo) + (i + 0.5) / n
                                * (math.log(hi) - math.log(lo)))))
            for i in range(n)]


class _Keys:
    """Elle's rotating key pool: ``count`` active keys, each retired
    after ``max_writes`` appends for the next fresh key."""

    def __init__(self, count, max_writes):
        self.active = list(range(count))
        self.writes = {k: 0 for k in self.active}
        self.next_key = count
        self.max_writes = max_writes
        self.counter = 0

    def txn(self, rng, cfg):
        n = cfg["min_txn_length"] + rng.randrange(
            cfg["max_txn_length"] - cfg["min_txn_length"] + 1)
        mops = []
        for _ in range(n):
            k = self.active[rng.randrange(len(self.active))]
            if rng.random() < cfg["read_fraction"]:
                mops.append(("r", k, None))
                continue
            self.counter += 1
            mops.append(("append", k, self.counter))
            self.writes[k] += 1
            if self.writes[k] >= self.max_writes:
                self.active[self.active.index(k)] = self.next_key
                self.writes[self.next_key] = 0
                self.next_key += 1
        return tuple(mops)


class _Store:
    """Each key's list, with the commit time of every element: the
    state at any earlier time is a prefix of the list."""

    def __init__(self):
        self.lists = {}
        self.times = {}

    def value(self, k, t=None):
        lst = self.lists.get(k, [])
        if t is None:
            return lst
        return lst[:bisect.bisect_right(self.times.get(k, []), t)]

    def run(self, rng, mops, now, stale_p, lag_lo, lag_hi):
        """Execute ``mops`` atomically at ``now``; returns the
        completed micro-ops."""
        view = {}
        out = []
        for f, k, v in mops:
            if f == "r":
                if k not in view:
                    if stale_p and rng.random() < stale_p:
                        lag = math.exp(rng.uniform(math.log(lag_lo),
                                                   math.log(lag_hi)))
                        view[k] = list(self.value(k, now - lag))
                    else:
                        view[k] = list(self.value(k))
                out.append((f, k, tuple(view[k])))
            else:
                if k not in view:
                    view[k] = list(self.value(k))
                view[k].append(v)
                out.append((f, k, v))
        self.apply(mops, now)
        return tuple(out)

    def apply(self, mops, now):
        for f, k, v in mops:
            if f == "append":
                self.lists.setdefault(k, []).append(v)
                self.times.setdefault(k, []).append(now)


def simulate(rng, n_txns, cfg, faulty):
    """One test of ``n_txns`` invoked transactions: its events."""
    conc = cfg["concurrency"]
    lat_lo, lat_mean = cfg["latency_s"]
    stale_p = cfg["stale_read_p"] if faulty else 0.0
    lag_lo, lag_hi = cfg["stale_lag_s"]
    keys = _Keys(cfg["key_count"], cfg["max_writes_per_key"])
    store = _Store()
    events = []
    clock = [0]
    heap = []       # (sim time, seq, kind, process, mops, crashed)
    seq = [0]
    invoked = [0]

    def stamp(t):
        # strictly increasing ns in the order events enter the history
        clock[0] = max(clock[0] + 1, int(t * 1e9))
        return clock[0]

    def invoke(p, t):
        """Invoke process ``p``'s next transaction at ``t``; aborts
        refused at begin complete at once, and the process goes on."""
        while invoked[0] < n_txns:
            invoked[0] += 1
            mops = keys.txn(rng, cfg)
            events.append((INVOKE, p, mops, stamp(t)))
            if rng.random() < cfg["abort_p"]:
                events.append((FAIL, p, mops, stamp(t)))
                continue
            lat = lat_lo + rng.expovariate(1.0 / (lat_mean - lat_lo))
            crashed = rng.random() < cfg["crash_p"]
            seq[0] += 1
            heapq.heappush(heap, (t + lat * rng.uniform(0.2, 0.8), seq[0],
                                  "commit", p, mops, crashed))
            seq[0] += 1
            heapq.heappush(heap, (t + lat, seq[0], "complete", p, None,
                                  False))
            return

    done = {}
    for p in range(conc):
        invoke(p, 0.0)
    while heap:
        t, _s, kind, p, mops, crashed = heapq.heappop(heap)
        if kind == "commit":
            if crashed:
                if rng.random() < 0.5:
                    store.apply(mops, t)
                done[p] = (INFO, mops)
            else:
                done[p] = (OK, store.run(rng, mops, t, stale_p, lag_lo,
                                         lag_hi))
            continue
        typ, value = done.pop(p)
        # a crash is recorded with the invocation's micro-ops
        events.append((typ, p, value, stamp(t)))
        if typ == INFO:
            p += conc
        invoke(p, t)
    return events


def faulty_tests(n, fraction):
    """The ladder positions that run against the lagging store: every
    ``1 / fraction``-th from the smallest, so that every seed's pool
    holds the same faulty sizes and the window's first requests (its
    order starts 0, n/2, ...) hold faulty ones."""
    return set(range(0, n, round(1 / fraction)))


def make_pool(cfg, traffic, seed):
    """The cell's traffic pool: ``pool_tests`` requests of one test
    each, at the ladder's transaction counts, ascending; a
    ``faulty_fraction`` of them faulty (:func:`faulty_tests`)."""
    rng = random.Random(seed)
    lo, hi = traffic["txns_per_test"]
    sizes = ladder(lo, hi, traffic["pool_tests"])
    faulty = faulty_tests(len(sizes), cfg["faulty_fraction"])
    return [[(i in faulty, simulate(rng, n, cfg, i in faulty))]
            for i, n in enumerate(sizes)]


def request_size(request, traffic):
    """(transactions invoked, history events) of one request."""
    events = [e for _faulty, evs in request for e in evs]
    return sum(1 for e in events if e[0] == INVOKE), len(events)
