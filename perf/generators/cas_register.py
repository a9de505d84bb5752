"""The CAS-register generator: histories from a configuration, a
traffic file's parameters and a seed, as plain tuples.

A history is a list of events ``(type, process, f, value)`` with
``type`` in invoke/ok/fail/info, ``f`` in read/write/cas and a cas
value the pair ``(old, new)``.  Nothing here imports the program; the
cell's entry (``perf/entries/``) turns events into the program's ops.
A corruption writes one of ``CORRUPT_VALUES``, above every value an
honest op writes: ``perf/reference/cas_register.py`` knows the verdict
of a history from that.

``single_history`` is a copy of ``jepsen_tpu.synth.generate_history``
(same RNG stream).  ``keyed_test`` simulates
``jepsen.tests.linearizable-register``: keys run one after another on
one group of threads, half of them reading and half mixing
``[w cas cas]``, each key's ops limited to the jittered per-key limit
and its processes to the process limit, a crashed process retired
under a fresh id (``process + threads``).
"""

from __future__ import annotations

import math
import random

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"
#: the values a corruption writes, which no honest op ever writes
CORRUPT_VALUES = (7, 8, 9)


def single_history(rng, n_procs, n_ops, crash_p, corrupt, n_values,
                   replace_crashed=False):
    """One simulated concurrent CAS-register execution: valid by
    construction unless ``corrupt``, which rewrites one ok read or
    write to a value nobody wrote (a cas is left as it was)."""
    state = 0
    hist = []
    pending = {}
    idle = list(range(n_procs))
    next_pid = n_procs
    values = list(range(1, n_values + 1))
    ops_done = 0
    while ops_done < n_ops or pending:
        if idle and ops_done < n_ops and (not pending or rng.random() < 0.6):
            p = rng.choice(idle)
            idle.remove(p)
            f = rng.choice(["read", "write", "cas"])
            if f == "read":
                v = None
            elif f == "write":
                v = rng.choice(values)
            else:
                v = (rng.choice(values + [state]), rng.choice(values))
            hist.append((INVOKE, p, f, v))
            pending[p] = (f, v)
            ops_done += 1
            continue
        p = rng.choice(list(pending.keys()))
        f, v = pending.pop(p)
        if rng.random() < crash_p:
            # crashed: took effect or not, secretly; the id is retired
            if f == "write" and rng.random() < 0.5:
                state = v
            elif f == "cas" and rng.random() < 0.5 and state == v[0]:
                state = v[1]
            hist.append((INFO, p, f, v))
            if replace_crashed:
                idle.append(next_pid)
                next_pid += 1
        else:
            if f == "read":
                v = state
            elif f == "write":
                state = v
            elif state == v[0]:
                state = v[1]
            else:
                hist.append((FAIL, p, f, v))
                idle.append(p)
                continue
            hist.append((OK, p, f, v))
            idle.append(p)
        if not idle and not pending:
            break
    if corrupt and len(hist) > 2:
        oks = [i for i, e in enumerate(hist) if e[0] == OK]
        if oks:
            i = rng.choice(oks)
            t, p, f, _v = hist[i]
            if f in ("read", "write"):
                hist[i] = (t, p, f, rng.choice(CORRUPT_VALUES))
    return hist


def key_history(rng, threads, pids, n_ops, process_limit, crash_p,
                n_values):
    """One key of a linearizable-register test: ``threads`` threads,
    the first half reading, the rest mixing [w cas cas]; ``pids`` maps
    thread -> current process and is updated in place when a crash
    retires one.  A thread whose next process would exceed the key's
    ``process_limit`` distinct processes stops working this key."""
    state = 0
    hist = []
    pending = {}
    idle = list(range(threads))
    seen = {pids[t] for t in idle}
    values = list(range(1, n_values + 1))
    readers = threads // 2
    ops_done = 0
    while ops_done < n_ops or pending:
        if idle and ops_done < n_ops and (not pending or rng.random() < 0.6):
            t = rng.choice(idle)
            idle.remove(t)
            p = pids[t]
            if t < readers:
                f, v = "read", None
            else:
                f = rng.choice(["write", "cas", "cas"])
                v = (rng.choice(values) if f == "write"
                     else (rng.choice(values), rng.choice(values)))
            hist.append((INVOKE, p, f, v))
            pending[t] = (f, v)
            ops_done += 1
            continue
        if not pending:
            break
        t = rng.choice(list(pending.keys()))
        f, v = pending.pop(t)
        p = pids[t]
        if rng.random() < crash_p:
            if f == "write" and rng.random() < 0.5:
                state = v
            elif f == "cas" and rng.random() < 0.5 and state == v[0]:
                state = v[1]
            hist.append((INFO, p, f, v))
            pids[t] = p + threads
            if len(seen) < process_limit:
                seen.add(pids[t])
                idle.append(t)
            continue
        if f == "read":
            v = state
        elif f == "write":
            state = v
        elif state == v[0]:
            state = v[1]
        else:
            hist.append((FAIL, p, f, v))
            idle.append(t)
            continue
        hist.append((OK, p, f, v))
        idle.append(t)
    return hist


def corrupt_history(rng, hist):
    """Rewrite one ok read or write of ``hist`` in place, as
    ``single_history`` does."""
    oks = [i for i, e in enumerate(hist) if e[0] == OK]
    if oks:
        i = rng.choice(oks)
        t, p, f, _v = hist[i]
        if f in ("read", "write"):
            hist[i] = (t, p, f, rng.choice(CORRUPT_VALUES))


def keyed_test(rng, n_keys, cfg, crash_p, corrupt_fraction):
    """One test's worth of keys: a list of ``(key, history)`` in the
    order the keys ran."""
    threads = cfg["threads_per_key"]
    lo, hi = cfg["limit_jitter"]
    pids = list(range(threads))
    keys = []
    for k in range(n_keys):
        n_ops = int((lo + rng.random() * (hi - lo)) * cfg["per_key_limit"])
        h = key_history(rng, threads, pids, max(1, n_ops),
                        cfg["process_limit"], crash_p, cfg["values"])
        if rng.random() < corrupt_fraction:
            corrupt_history(rng, h)
        keys.append((k, h))
    return keys


def ladder(lo, hi, n):
    """``n`` key counts at the quantiles (i + 0.5) / n of the
    log-uniform distribution over [lo, hi]."""
    return [int(round(math.exp(math.log(lo) + (i + 0.5) / n
                                * (math.log(hi) - math.log(lo)))))
            for i in range(n)]


def make_pool(cfg, traffic, seed):
    """The cell's traffic pool from the configuration, the traffic
    parameters and ``seed``: a list of requests, each a list of items.
    An item is one history (``single_key``) or one test's
    ``(key, history)`` list (``keyed``)."""
    rng = random.Random(seed)
    kind = traffic["shape"]
    if kind == "single_key":
        per_req = cfg["histories_per_request"]
        reqs = []
        for _ in range(traffic["pool_requests"]):
            reqs.append([
                single_history(
                    rng, cfg["processes"], cfg["ops_per_history"],
                    traffic["crash_p"],
                    rng.random() < cfg["corrupt_fraction"], cfg["values"],
                    replace_crashed=traffic["replace_crashed"])
                for _ in range(per_req)])
        return reqs
    if kind == "keyed":
        lo, hi = traffic["keys_per_test"]
        # the ladder is fixed and ascending; the seed draws the histories
        return [[keyed_test(rng, n, cfg, traffic["crash_p"],
                            traffic["corrupt_key_fraction"])]
                for n in ladder(lo, hi, traffic["pool_tests"])]
    raise ValueError(f"unknown traffic shape {kind!r}")


def client_ops(hist):
    """Invocations in a history: what ``ops_per_s`` counts."""
    return sum(1 for e in hist if e[0] == INVOKE)


def request_size(request, traffic):
    """(client ops, events) of one request: the invocations
    ``ops_per_s`` counts, and the history events the kernels' bytes
    are counted from."""
    hists = request if traffic["shape"] == "single_key" else [
        h for _k, h in request[0]]
    return (sum(client_ops(h) for h in hists),
            sum(len(h) for h in hists))
