"""The closure screen's least work, counted from the graphs whatever
implements the closure: each screened SCC of ``n`` live vertices must
at least multiply each filter plane once, an n x n boolean product,
and each nonadjacent walk query once, a 2n x 2n one (its lifted
graph), at 2 m^3 operations a product of m x m matrices.  The
``log2 n`` squarings a closure takes are not counted: the early-exit
mode runs fewer."""

import re

from _spans import traced_requests

#: the screen executable's name in the device trace (``XLA Modules``)
SCREEN_MODULE = re.compile(r"jit_screen")


def least_ops(n, planes, walks):
    return 2.0 * n ** 3 * planes + 2.0 * (2 * n) ** 3 * walks


def screen_ops(spans):
    """Summed least operations of the SCCs the ``elle/screen`` spans
    screened."""
    total = 0.0
    for s in spans:
        a = s.get("attrs", {})
        if not a.get("scc_vertices"):
            continue
        for n, f, q in zip(*(a[k].split(",") for k in
                             ("scc_vertices", "planes", "walks"))):
            total += least_ops(int(n), int(f), int(q))
    return total


def screen_seconds(run):
    """Device seconds of the screen executable's runs in the trace,
    summed over the chips, or None."""
    if run.profile is None:
        return None
    ns = sum(t for c in run.profile["chips"]
             for name, (t, _n) in c["modules"].items()
             if SCREEN_MODULE.search(name))
    return ns / 1e9 if ns else None


def traced_spans(run, name):
    """The finished spans called ``name`` that began inside a request
    the trace covers."""
    spans = []
    for r in traced_requests(run):
        lo = r.mono0
        hi = r.mono0 + (r.t1 - r.t0) * 1e9
        spans += [s for s in run.spans if s["name"] == name
                  and s.get("t1") is not None and lo <= s["t0"] <= hi]
    return spans
