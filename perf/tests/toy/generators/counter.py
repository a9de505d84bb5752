"""Counter histories: concurrent ``add`` (of 1 to ``max_add``) and
``read`` on one counter that starts at 0, as plain tuples
``(type, process, f, value)``.  A crashed op (``info``) took effect or
not, and its process is retired under a fresh id.  A corrupted history
has one ok read of more than was ever added."""

from __future__ import annotations

import random


def history(rng, n_procs, n_ops, max_add, crash_p, corrupt):
    total = 0
    hist = []
    pending = {}
    idle = list(range(n_procs))
    next_pid = n_procs
    done = 0
    while done < n_ops or pending:
        if idle and done < n_ops and (not pending or rng.random() < 0.6):
            p = idle.pop(rng.randrange(len(idle)))
            f = rng.choice(["add", "read"])
            v = rng.randint(1, max_add) if f == "add" else None
            hist.append(("invoke", p, f, v))
            pending[p] = (f, v)
            done += 1
            continue
        p = rng.choice(list(pending))
        f, v = pending.pop(p)
        if rng.random() < crash_p:
            if f == "add" and rng.random() < 0.5:
                total += v
            hist.append(("info", p, f, v))
            idle.append(next_pid)
            next_pid += 1
            continue
        if f == "add":
            total += v
        else:
            v = total
        hist.append(("ok", p, f, v))
        idle.append(p)
    reads = [i for i, e in enumerate(hist) if e[0] == "ok" and e[2] == "read"]
    if corrupt and reads:
        i = rng.choice(reads)
        t, p, f, _v = hist[i]
        hist[i] = (t, p, f, max_add * n_ops + 1)
    return hist


def make_pool(cfg, traffic, seed):
    rng = random.Random(seed)
    return [[history(rng, cfg["processes"], cfg["ops_per_history"],
                     cfg["max_add"], traffic["crash_p"],
                     rng.random() < cfg["corrupt_fraction"])
             for _ in range(cfg["histories_per_request"])]
            for _ in range(traffic["pool_requests"])]


def request_size(request, traffic):
    return (sum(1 for h in request for e in h if e[0] == "invoke"),
            sum(len(h) for h in request))
