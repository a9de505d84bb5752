"""The plain reference: is a CAS-register history linearizable?

Semantics are Knossos' ``cas-register`` under ``jepsen.checker/
linearizable``: a register that starts at ``init``; ``read`` returns
its value, ``write v`` sets it, ``cas (a, b)`` sets it to ``b`` if it
holds ``a``.  An ``ok`` op took effect once, between its invocation and
its completion; a ``fail`` op never took effect; an ``info`` op (and
an invocation with no completion) may take effect once at any later
time, or never.

The search keeps every configuration the history allows: the
register's value, the open ops already linearized, and which crashed
ops have fired.  At each ``ok`` it closes the set under linearizing
any open op, then keeps the configurations in which that op has been
linearized.  A configuration in which fewer crashed ops have fired
than another with the same value and the same open ops linearized can
do all that the other can, so the larger one is dropped.  Nothing here
imports the program.

``known`` and ``search`` give the harness (``perf/verify.py``) an
item's labels: ``"valid?"`` for a ``single_key`` history, and for a
``keyed`` test one ``("key", k)`` per key beside the merged
``"valid?"``.
"""

from __future__ import annotations

from collections import deque


def _prepare(history, crashed_take_effect):
    """Events ``("inv", pid, f, value, crashed)`` and ``("ok", pid, ...)``
    with fail ops and crashed reads dropped and each ok op carrying its
    completion's value, as Knossos' ``history/complete`` does; crashed
    writes and cas stay open forever (or are dropped, for the
    control)."""
    completion = {}
    open_by_pid = {}
    for i, (t, p, f, v) in enumerate(history):
        if t == "invoke":
            open_by_pid[p] = i
        else:
            j = open_by_pid.pop(p, None)
            if j is not None:
                completion[j] = (t, v)
    out = []
    for i, (t, p, f, v) in enumerate(history):
        if t == "invoke":
            ct, cv = completion.get(i, ("info", v))
            if ct == "fail":
                continue
            if ct == "info":
                if f == "read" or not crashed_take_effect:
                    continue
                out.append(("inv", p, f, v, True))
            else:
                out.append(("inv", p, f, cv, False))
        elif t == "ok":
            out.append(("ok", p, None, None, False))
    return out


def _step(state, f, v):
    """The register's next value, or None where the op cannot apply."""
    if f == "read":
        return state if v == state else None
    if f == "write":
        return v
    return v[1] if state == v[0] else None


def _add(table, state, live, fired):
    """Add a configuration unless one with fewer fired crashed ops
    covers it; drop those it covers.  Returns True if added."""
    key = (state, live)
    firedsets = table.get(key)
    if firedsets is None:
        table[key] = [fired]
        return True
    for g in firedsets:
        if g & fired == g:
            return False
    table[key] = [g for g in firedsets if g & fired != fired] + [fired]
    return True


def linearizable(history, init=0, crashed_take_effect=True):
    """True if ``history`` (events ``(type, process, f, value)``) is
    linearizable for a CAS register starting at ``init``."""
    events = _prepare(history, crashed_take_effect)
    ops = {}            # slot -> (f, v, crashed)
    slot_of = {}        # pid -> slot of its open ok op
    free = []
    n_slots = 0
    configs = {(init, 0): [0]}   # (state, live mask) -> [fired masks]
    for kind, p, f, v, crashed in events:
        if kind == "inv":
            if free:
                s = free.pop()
            else:
                s = n_slots
                n_slots += 1
            ops[s] = (f, v, crashed)
            if not crashed:
                slot_of[p] = s
            continue
        done = slot_of.pop(p)
        bit = 1 << done
        # close the set under linearizing any open op
        table = {}
        queue = deque()
        for (st, live), fs in configs.items():
            for fired in fs:
                if _add(table, st, live, fired):
                    queue.append((st, live, fired))
        while queue:
            st, live, fired = queue.popleft()
            if fired not in table.get((st, live), ()):
                continue   # dropped: a smaller fired set covers it
            taken = live | fired
            for s, (of, ov, crashed) in ops.items():
                b = 1 << s
                if taken & b:
                    continue
                nst = _step(st, of, ov)
                if nst is None:
                    continue
                if crashed:
                    nl, nf = live, fired | b
                else:
                    nl, nf = live | b, fired
                if _add(table, nst, nl, nf):
                    queue.append((nst, nl, nf))
        configs = {}
        for (st, live), fs in table.items():
            if live & bit:
                configs[(st, live & ~bit)] = fs
        if not configs:
            return False
        del ops[done]
        free.append(done)
    return True


def known_verdict(hist, n_values):
    """The verdict the generator knows (a copy of
    ``jepsen_tpu.synth.known_verdict``): True for an honest history (it
    linearizes at each completion), False where an ok read returned a
    value nobody wrote (the generator's corruptions write values above
    ``n_values``), None where a corrupted write leaves it open."""
    for t, _p, f, v in hist:
        if t == "ok" and f in ("read", "write") and v is not None \
                and v > n_values:
            return False if f == "read" else None
    return True


def known(cfg, traffic, item):
    """Every label of one pool item with the verdict the generator
    knows for it, or None: ``"valid?"`` for a ``single_key`` history;
    for a ``keyed`` test one ``("key", k)`` per key and the merged
    ``"valid?"``, False once a key is known invalid, True once every
    key is known valid."""
    if traffic["shape"] == "single_key":
        return {"valid?": known_verdict(item, cfg["values"])}
    out = {("key", k): known_verdict(h, cfg["values"]) for k, h in item}
    verdicts = list(out.values())
    out["valid?"] = (False if False in verdicts
                     else True if None not in verdicts else None)
    return out


def search(cfg, traffic, item, labels, crashed_take_effect=True):
    """The labels asked for, each decided by ``linearizable`` alone; a
    keyed test's merged ``"valid?"`` by searching every key.  With
    ``crashed_take_effect`` false, the control's search."""
    def lin(hist):
        return linearizable(hist, cfg["init"], crashed_take_effect)

    if traffic["shape"] == "single_key":
        return {"valid?": lin(item)}
    keys = {("key", k): h for k, h in item}
    todo = keys if "valid?" in labels else {lb: keys[lb] for lb in labels}
    out = {lb: lin(h) for lb, h in todo.items()}
    if "valid?" in labels:
        out["valid?"] = all(out.values())
    return out
