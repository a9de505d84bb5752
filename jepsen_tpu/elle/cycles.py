"""Cycle classification: SCCs → anomaly-typed witness cycles.

Adya's phenomena as edge-type profiles over the dependency graph:

- G0            cycle of only ww edges
- G1c           cycle of ww/wr edges (not G0)
- G-single      cycle with exactly one rw edge, rest ww/wr
- G-nonadjacent cycle with ≥2 rw edges, no two cyclically adjacent —
                still impossible under snapshot isolation
- G2-item       cycle with ≥1 rw edges (≥2, some adjacent, once the
                previous two are excluded)

With realtime/process graphs unioned in, the same profiles allowing
those edges yield the -realtime / -process variants (e.g. a cycle of ww
+ realtime edges is G0-realtime, proscribed by strict serializability
but not plain serializability).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Set, Tuple

from .graph import (
    Graph,
    INDETERMINATE,
    WW,
    WR,
    RW,
    PROCESS,
    REALTIME,
    cycle_rels,
    find_cycle,
    find_cycle_with,
    find_nonadjacent_cycle,
    strongly_connected_components,
)

_ORDER = [PROCESS, REALTIME]


def _fmt_cycle(g: Graph, cyc: List[Any]) -> dict:
    steps = []
    for a, b in zip(cyc, cyc[1:]):
        steps.append(
            {"from": repr(a), "rels": sorted(g.edge_rels(a, b)), "to": repr(b)}
        )
    return {"cycle": [repr(v) for v in cyc], "steps": steps}


def _suffix(rels_used: Set[str]) -> str:
    if REALTIME in rels_used:
        return "-realtime"
    if PROCESS in rels_used:
        return "-process"
    return ""


def _induced(g: Graph, scc: List[Any]) -> Graph:
    """The subgraph of ``g`` on one SCC's vertices, with every edge
    between two of them in ``g``'s own successor order: a search over
    it visits vertices exactly as a search over ``g`` that skips
    non-members does."""
    h = Graph()
    members = set(scc)
    h.vertices = members
    for a in scc:
        kept = {b: rels for b, rels in g.out.get(a, {}).items()
                if b in members}
        if kept:
            h.out[a] = kept
    return h


def scc_parts(g: Graph) -> List[Tuple[List[Any], Graph]]:
    """Tarjan over the union of ``g``'s edges: each nontrivial SCC
    with its induced subgraph.  Any cycle, under any relation filter,
    lies inside one SCC of the union, so every search (and every
    device screen) can run on the SCCs alone."""
    return [(scc, _induced(g, scc))
            for scc in strongly_connected_components(g)]


def classify(g: Graph, screens: Optional[list] = None,
             parts: Optional[list] = None) -> Dict[str, list]:
    """Find one witness cycle per anomaly type per SCC.

    ``parts`` is :func:`scc_parts` of ``g`` (computed here if not
    given).  With ``screens`` (one per part: the device's
    per-relation-filter cycle membership masks and nonadjacent-rw walk
    masks of that SCC's subgraph, :func:`screen_for_graphs`, or
    ``None`` for an SCC it did not screen), every ladder rung the
    device has proven empty *under that rung's relation filter* is
    skipped outright: a skipped search is one the CPU would provably
    have answered None, so the output is byte-identical to the
    unscreened run (the fuzz corpus pins it) — the BFS witness
    searches only run on rungs already proven cyclic."""
    if parts is None:
        parts = scc_parts(g)
    anomalies: Dict[str, list] = {}
    for i, (scc, h) in enumerate(parts):
        _classify_scc(h, scc, None if screens is None else screens[i],
                      anomalies)
    return anomalies


def _on_cycles(sub: Graph, scc: List[Any]) -> List[Any]:
    """The vertices of ``scc`` that lie on some cycle of ``sub``, in
    ``scc``'s order.  A cycle of ``sub`` passes through none of the
    others, nor does any path that a BFS for one can still close, so
    :func:`find_cycle` and :func:`find_cycle_with` over these alone
    return exactly what they return over all of ``scc``, without the
    searches from starts that cannot close."""
    on: Set[Any] = set()
    for comp in strongly_connected_components(sub):
        on.update(comp)
    return [v for v in scc if v in on]


def _classify_scc(g: Graph, scc: List[Any], screen, anomalies) -> None:
    """The anomaly ladder over one SCC ``scc`` whose induced subgraph
    is ``g``, recording at most one cycle into ``anomalies``."""
    from . import encode as encode_mod

    def record(name: str, cyc: List[Any]) -> None:
        anomalies.setdefault(name, []).append(_fmt_cycle(g, cyc))

    def rung_empty(mask: int) -> bool:
        """Device-proven: this SCC has no cycle in the subgraph of
        edges carrying a relation in ``mask``."""
        if screen is None:
            return False
        mem = screen.members(mask)
        return mem is not None and not mem

    def walk_empty(rest_mask: int) -> bool:
        """Device-proven: no nonadjacent-rw closed walk through any
        vertex of this SCC (⇒ find_nonadjacent_cycle's walk BFS would
        see nothing and answer None)."""
        if screen is None:
            return False
        w = screen.nonadj(encode_mod.RW_BIT, rest_mask)
        return w is not None and not w

    # Most-severe-first: G0, then G1c, then G-single, then G2-item.
    has_rw = lambda rels: RW in rels  # noqa: E731

    if rung_empty(encode_mod.WW_BIT):
        cyc = None
    else:
        sub = g.filtered(lambda rels: bool(rels & {WW}))
        cyc = find_cycle(sub, _on_cycles(sub, scc))
    if cyc is not None:
        record("G0", cyc)
        return

    if rung_empty(encode_mod.WW_BIT | encode_mod.WR_BIT):
        cyc = None
    else:
        sub = g.filtered(lambda rels: bool(rels & {WW, WR}))
        cyc = find_cycle(sub, _on_cycles(sub, scc))
    if cyc is not None:
        record("G1c", cyc)
        return

    # every remaining plain rung needs a cycle inside the ww|wr|rw
    # subgraph; one device mask screens all three, and where none did
    # (an SCC past the device's cap), the host's Tarjan over it does
    rw_rungs_empty = rung_empty(
        encode_mod.WW_BIT | encode_mod.WR_BIT | encode_mod.RW_BIT
    )
    if not rw_rungs_empty:
        plain = g.filtered(lambda rels: bool(rels & {WW, WR, RW}))
        plain_on = _on_cycles(plain, scc)
        rw_rungs_empty = not plain_on

    cyc = None if rw_rungs_empty else find_cycle_with(
        g,
        plain_on,
        want=has_rw,
        rest=lambda rels: bool(rels & {WW, WR}),
        want_count=1,
    )
    if cyc is not None:
        record("G-single", cyc)
        return

    # G-nonadjacent: ≥2 rw edges, none cyclically adjacent — still a
    # snapshot-isolation violation (SI cycles need two adjacent rws)
    if rw_rungs_empty or walk_empty(encode_mod.WW_BIT | encode_mod.WR_BIT):
        cyc = None
    else:
        cyc = find_nonadjacent_cycle(
            g,
            scc,
            want=has_rw,
            rest=lambda rels: bool(rels & {WW, WR}),
        )
    if cyc is INDETERMINATE:
        # simple-cycle search budget exhausted: a G-nonadjacent may
        # exist in this SCC.  Record the uncertainty (result() turns
        # it into valid?=unknown for models that proscribe the
        # anomaly) and fall through to the definite G2-item witness.
        anomalies.setdefault("G-nonadjacent-indeterminate", []).append(
            {
                "scc-size": len(scc),
                "reason": "simple-cycle search budget exhausted",
            }
        )
    elif cyc is not None:
        record("G-nonadjacent", cyc)
        return

    cyc = None if rw_rungs_empty else find_cycle(plain, plain_on)
    if cyc is not None:
        record("G2-item", cyc)
        return

    # Cycle requires process/realtime edges: -realtime/-process
    # variants of the same ladder.
    pr = encode_mod.PR_MASK
    for want_rels, name in (
        ({WW}, "G0"),
        ({WW, WR}, "G1c"),
        (None, "G-single"),
        ("nonadjacent", "G-nonadjacent"),
        ({WW, WR, RW}, "G2-item"),
    ):
        if name == "G-single":
            cyc = None if rung_empty(encode_mod.ALL_MASK) else (
                find_cycle_with(
                    g,
                    scc,
                    want=has_rw,
                    rest=lambda rels: bool(
                        rels & {WW, WR, PROCESS, REALTIME}
                    ),
                    want_count=1,
                )
            )
        elif name == "G-nonadjacent":
            cyc = (
                None
                if walk_empty(encode_mod.WW_BIT | encode_mod.WR_BIT | pr)
                else find_nonadjacent_cycle(
                    g,
                    scc,
                    want=has_rw,
                    rest=lambda rels: bool(
                        rels & {WW, WR, PROCESS, REALTIME}
                    ),
                )
            )
            if cyc is INDETERMINATE:
                # this rung's hypothetical cycle needs process or
                # realtime edges (the plain rung already answered
                # definitively or recorded its own marker), so only
                # the suffixed variants are uncertain — the plain
                # marker would wrongly degrade serializable/SI
                # verdicts that are provably clean
                for suffixed in (
                    "G-nonadjacent-process-indeterminate",
                    "G-nonadjacent-realtime-indeterminate",
                ):
                    anomalies.setdefault(suffixed, []).append(
                        {
                            "scc-size": len(scc),
                            "reason": "simple-cycle search budget exhausted",
                        }
                    )
                cyc = None
        else:
            mask = encode_mod.rel_mask(want_rels) | pr
            if rung_empty(mask):
                cyc = None
            else:
                sub = g.filtered(
                    lambda rels, wr=want_rels: bool(
                        rels & (wr | {PROCESS, REALTIME})
                    )
                )
                cyc = find_cycle(sub, _on_cycles(sub, scc))
        if cyc is not None:
            used: Set[str] = set()
            for rels in cycle_rels(g, cyc):
                used |= rels
            record(name + _suffix(used), cyc)
            return


#: winner cache for the device-vs-CPU cycle screen, keyed by
#: (vertex-bucket, batch-size-bucket) — one runtime calibration per key
#: per process (see cyclic_graph_mask).  "cpu" is also the terminal
#: state when the device path errors or ever disagrees with the SCC
#: reference.
_SCREEN_CHOICE: dict = {}

#: never even calibrate the O(n³) closure kernel past this many
#: vertices: on the CPU backend it loses to SCC well before n=256,
#: while the chip's matrix unit is expected to keep it ahead there
#: (not measured on the chip on today's code), which is why the cap
#: sits at 512 and not lower — and a first-touch calibration on a huge
#: padded matrix would burn minutes proving the obvious.  classify_graphs
#: applies it per SCC of a graph's union, not to the whole graph
DEVICE_SCREEN_MAX_VERTICES = 512


def _screen_bucket(n: int) -> int:
    return 1 << max(4, int(n - 1).bit_length())


def _cpu_screen(graphs):
    import numpy as np

    return np.array(
        [bool(strongly_connected_components(g)) for g in graphs]
    )


def _adjacency_mats(graphs):
    return [g.adjacency()[1] for g in graphs]


def _device_screen(graphs, mats=None):
    from ..ops import cycles as ops_cycles

    if mats is None:
        mats = _adjacency_mats(graphs)
    return ops_cycles.has_cycle_batch(mats)


def cyclic_graph_mask(graphs: List[Graph], use_device: Optional[bool] = None):
    """Batched cycle screening: which of these graphs contain a cycle at
    all?  Pads adjacency matrices to a common bucket and runs the
    boolean-closure kernel (jepsen_tpu.ops.cycles) in one dispatch —
    the Elle-on-TPU formulation from SURVEY.md §7 step 8.

    Routing between the device kernel and per-graph CPU SCC is
    SELF-CALIBRATING: the first batch at each (vertex-count,
    batch-size) bucket pair runs BOTH paths (the device one twice, so
    compile time doesn't pollute the measurement), cross-checks their
    answers, and caches the faster engine for that pair on the backend
    actually in use — a band measured on this host's CPU would
    silently misroute on a real chip, where the crossover sits
    elsewhere, and a 1-graph batch's dispatch overhead says nothing
    about a 4096-graph batch's.  A cross-check mismatch pins the pair
    to CPU permanently (the screen must never trade correctness for
    speed); a device error propagates, and graphs past
    DEVICE_SCREEN_MAX_VERTICES skip calibration entirely."""
    import logging
    import time

    import numpy as np

    if not graphs:
        return np.zeros((0,), dtype=bool)
    if use_device is not None:
        return (
            _device_screen(graphs) if use_device else _cpu_screen(graphs)
        )

    biggest = max(len(g.vertices) for g in graphs)
    if biggest > DEVICE_SCREEN_MAX_VERTICES:
        return _cpu_screen(graphs)
    key = (_screen_bucket(biggest), _screen_bucket(len(graphs)))
    choice = _SCREEN_CHOICE.get(key)
    if choice == "device":
        return _device_screen(graphs)
    if choice == "cpu":
        return _cpu_screen(graphs)

    # calibrate: both engines answer this batch; the winner takes the
    # bucket pair.  The batch's verdicts come for free (cross-checked).
    t0 = time.perf_counter()
    cpu_out = _cpu_screen(graphs)
    t_cpu = time.perf_counter() - t0
    _device_screen(graphs, _adjacency_mats(graphs))  # warm/compile
    # the timed run pays full production cost — including adjacency
    # construction, which the cached-choice path pays on every call
    t0 = time.perf_counter()
    dev_out = _device_screen(graphs)
    t_dev = time.perf_counter() - t0
    if not np.array_equal(np.asarray(dev_out), cpu_out):
        logging.getLogger(__name__).warning(
            "elle cycle-screen device/CPU verdicts diverged; pinning %s "
            "to CPU",
            key,
        )
        _SCREEN_CHOICE[key] = "cpu"
        return cpu_out
    _SCREEN_CHOICE[key] = "device" if t_dev < t_cpu else "cpu"
    return cpu_out


# ---------------------------------------------------------------------------
# Device-screened classify: batched SCC/relation-filter screens through
# the production engine (ops.cycles → engine.execution.Executor)
# ---------------------------------------------------------------------------

#: winner cache for the screened-vs-CPU classify router, keyed by the
#: SCC batch's (vertex-bucket, batch-size-bucket); "cpu" is the
#: terminal state after any device error or cross-check mismatch
_CLASSIFY_CHOICE: dict = {}

#: below this many screenable SCCs the auto route stays on CPU —
#: dispatch overhead says nothing useful about tiny batches (the same
#: ≥16 gate version_graphs applies to its cycle screen)
ELLE_SCREEN_MIN_BATCH = 16


class GraphScreen:
    """One graph's device screens, decoded back into vertex space:
    ``members(mask)`` — the vertices on some cycle of the subgraph of
    edges carrying a relation in ``mask`` — and ``nonadj(want, rest)``
    — the vertices with a nonadjacent-want closed walk.  Queries
    canonicalize masks to the relation bits the graph actually has, so
    a graph with no process/realtime edges answers its suffixed-ladder
    rungs from the identical plain-relation closure.  Returns a set
    (possibly empty — a *definitive* no) or ``None`` for a filter the
    screen never computed (callers must then search, never skip)."""

    __slots__ = ("order", "present", "_members", "_walks", "_sets",
                 "_wsets")

    def __init__(self, enc, res):
        self.order = enc.order
        self.present = enc.present
        self._members = res.members
        self._walks = res.walks
        self._sets: dict = {}
        self._wsets: dict = {}

    def _vertex_set(self, arr):
        return frozenset(
            v for i, v in enumerate(self.order) if arr[i]
        )

    def members(self, mask: int):
        key = mask & self.present
        if key == 0:
            return frozenset()
        got = self._sets.get(key)
        if got is None:
            arr = self._members.get(key)
            if arr is None:
                return None
            got = self._sets[key] = self._vertex_set(arr)
        return got

    def nonadj(self, want: int, rest: int):
        if not (self.present & want):
            return frozenset()  # no want edge anywhere: trivially none
        key = (want, rest & self.present)
        got = self._wsets.get(key)
        if got is None:
            arr = self._walks.get(key)
            if arr is None:
                return None
            got = self._wsets[key] = self._vertex_set(arr)
        return got


def screen_for_graphs(graphs: List[Graph], executor=None, encs=None):
    """Encode and screen a batch of dependency graphs through the
    engine: returns ``(screens, route)`` with one
    :class:`GraphScreen` (or ``None`` — CPU fallback for that graph)
    per input.  With the checker service opted in
    (``JEPSEN_TPU_SERVICE``), screens ride ``POST /elle`` and coalesce
    with concurrent runs on the daemon's resident executor; otherwise
    they dispatch through an in-process
    :class:`~jepsen_tpu.engine.execution.Executor` (window, per-chip
    budget, mesh).  ``encs``: the graphs already encoded
    (:func:`~jepsen_tpu.elle.encode.encode_graph`)."""
    from . import encode as encode_mod
    from ..ops import cycles as ops_cycles

    if encs is None:
        encs = [encode_mod.encode_graph(g) for g in graphs]
    results = None
    route = "device"
    if executor is None:
        try:
            from ..serve import client as serve_client

            if serve_client.service_mode() != "off":
                results = serve_client.screen_graphs(encs)
                if results is not None:
                    route = "service"
        except Exception:  # noqa: BLE001 — any service trouble → local
            results = None
    if results is None:
        results = ops_cycles.screen_graphs(encs, executor=executor)
        route = "device"
    screens = [
        GraphScreen(enc, res) if res is not None else None
        for enc, res in zip(encs, results)
    ]
    return screens, route


def _screen_parts(parts_of: List[list], executor=None):
    """Screen every SCC of ``parts_of`` (per graph, its
    :func:`scc_parts`) that fits :data:`DEVICE_SCREEN_MAX_VERTICES`,
    pooled into one :func:`screen_for_graphs` batch.  Returns per graph
    the list of its SCCs' screens (``None`` where an SCC stayed on the
    CPU), and the route the batch took."""
    from . import encode as encode_mod
    from .. import obs

    screens: List[list] = [[None] * len(p) for p in parts_of]
    where = [(gi, si) for gi, p in enumerate(parts_of)
             for si, (scc, _h) in enumerate(p)
             if len(scc) <= DEVICE_SCREEN_MAX_VERTICES]
    route = "device"
    if where:
        subs = [parts_of[gi][si][1] for gi, si in where]
        with obs.span("elle/screen", cat="elle") as sp:
            encs = [encode_mod.encode_graph(h) for h in subs]
            got, route = screen_for_graphs(subs, executor=executor,
                                           encs=encs)
            if sp:
                # the workload, per SCC: live vertices, filter planes
                # and nonadjacent walk queries it asked the screen for
                sp.set("scc_vertices", ",".join(str(e.n) for e in encs))
                sp.set("planes", ",".join(str(len(e.masks)) for e in encs))
                sp.set("walks", ",".join(str(len(e.nonadj)) for e in encs))
        for (gi, si), s in zip(where, got):
            screens[gi][si] = s
    return screens, route


def _count_routes(parts_of: List[list], screens: Optional[List[list]] = None,
                  route: str = "cpu") -> None:
    """Route counters of the graphs served: each SCC by where it was
    screened (``cpu``: searched unscreened), and each graph ``cpu`` if
    any of its SCCs was, else by ``route`` (a graph with no nontrivial
    SCC needs no screen)."""
    from .. import obs

    if not obs.enabled():
        return
    by_route: Dict[str, int] = {}
    for i, p in enumerate(parts_of):
        ss = [None] * len(p) if screens is None else screens[i]
        for (scc, _h), s in zip(p, ss):
            r = "cpu" if s is None else route
            obs.count("jepsen_elle_scc_total", 1, route=r)
            obs.count("jepsen_elle_scc_vertices_total", len(scc), route=r)
        r = "cpu" if any(s is None for s in ss) else route
        by_route[r] = by_route.get(r, 0) + 1
    for r, k in by_route.items():
        obs.count("jepsen_elle_screen_route_total", k, route=r)


def _search(graphs: List[Graph], parts_of: List[list],
            screens: Optional[List[list]] = None) -> List[Dict[str, list]]:
    """:func:`classify` over every graph, under one ``elle/search``
    span: the witness searches the screens did not rule out."""
    from .. import obs

    with obs.span("elle/search", cat="elle"):
        return [classify(g, None if screens is None else screens[i], p)
                for i, (g, p) in enumerate(zip(graphs, parts_of))]


def _classify_screened(graphs: List[Graph], executor=None,
                       count: bool = True,
                       parts_of: Optional[List[list]] = None
                       ) -> List[Dict[str, list]]:
    """Classify with device screens of every SCC that fits the cap,
    recording the routes and the witness-search fallback evidence.
    ``count=False`` suppresses the counters — the calibration probes
    run this path without *serving* its results, and served-route
    accounting must reflect what callers actually received."""
    from .. import obs

    if parts_of is None:
        parts_of = [scc_parts(g) for g in graphs]
    screens, route = _screen_parts(parts_of, executor)
    out = _search(graphs, parts_of, screens)
    if count:
        _count_routes(parts_of, screens, route)
        # a graph whose union has a cycle still pays the witness
        # search — the "witness-search fallback" fraction
        n_fallback = sum(1 for p in parts_of if p)
        if n_fallback:
            obs.count("jepsen_elle_witness_fallback_total", n_fallback)
    return out


def _classify_route() -> str:
    import os

    return os.environ.get("JEPSEN_TPU_ELLE_SCREEN", "auto").strip().lower()


def classify_graphs(
    graphs: List[Graph],
    route: Optional[str] = None,
    executor=None,
) -> List[Dict[str, list]]:
    """Batched :func:`classify`: one host Tarjan per graph over the
    union of its edges, then every nontrivial SCC of up to
    :data:`DEVICE_SCREEN_MAX_VERTICES` vertices, from all graphs of the
    call, is screened on the device in shared engine dispatches, and
    CPU witness search runs only on the rungs the screens proved
    cyclic (and on every rung of an SCC past the cap).  A graph whose
    union has no nontrivial SCC needs neither.  ``route``: ``"cpu"``
    (pure host path — the byte-identity reference), ``"device"``
    (screens forced — smoke, fuzz, bench), or ``None``/``"auto"``
    (default; also ``JEPSEN_TPU_ELLE_SCREEN``): SELF-CALIBRATING per
    (SCC vertex-bucket, SCC batch-bucket) pair exactly like
    :func:`cyclic_graph_mask` — the first batch at each pair runs both
    paths, cross-checks anomalies for equality, and pins the faster
    engine; a device error or mismatch pins the pair to CPU
    permanently (the screens must never trade correctness for
    speed), and a batch of fewer than :data:`ELLE_SCREEN_MIN_BATCH`
    screenable SCCs stays on the CPU."""
    import logging
    import time

    from .. import obs

    route = (route or _classify_route()).lower()
    if not graphs:
        return []
    with obs.span("elle/scc", cat="elle"):
        parts_of = [scc_parts(g) for g in graphs]
    if route in ("device", "service"):
        return _classify_screened(graphs, executor=executor,
                                  parts_of=parts_of)
    sizes = [len(scc) for p in parts_of for scc, _h in p
             if len(scc) <= DEVICE_SCREEN_MAX_VERTICES]
    if route == "cpu" or len(sizes) < ELLE_SCREEN_MIN_BATCH:
        # auto: dispatch overhead says nothing useful about tiny
        # batches, which stay on the CPU
        _count_routes(parts_of)
        return _search(graphs, parts_of)
    key = (_screen_bucket(max(sizes)), _screen_bucket(len(sizes)))
    choice = _CLASSIFY_CHOICE.get(key)
    if choice == "device":
        return _classify_screened(graphs, executor=executor,
                                  parts_of=parts_of)
    if choice == "cpu":
        _count_routes(parts_of)
        return _search(graphs, parts_of)

    # calibrate: both engines classify this batch; the winner takes
    # the bucket pair, and a cross-check mismatch pins it to CPU —
    # correctness is never traded for speed.  A device error raises:
    # a kernel that fails on the chip must not pass as a CPU run
    t0 = time.perf_counter()
    cpu_out = _search(graphs, parts_of)
    t_cpu = time.perf_counter() - t0
    # count=False: these are probes — the CPU results below are what
    # the caller is served, so the route counter must say cpu
    _classify_screened(graphs, executor=executor, count=False,
                       parts_of=parts_of)  # warm/compile
    t0 = time.perf_counter()
    dev_out = _classify_screened(graphs, executor=executor, count=False,
                                 parts_of=parts_of)
    t_dev = time.perf_counter() - t0
    if dev_out != cpu_out:
        logging.getLogger(__name__).warning(
            "elle screened/CPU classify diverged; pinning %s to CPU", key,
        )
        obs.count("jepsen_elle_screen_mismatch_total")
        _CLASSIFY_CHOICE[key] = "cpu"
    else:
        _CLASSIFY_CHOICE[key] = "device" if t_dev < t_cpu else "cpu"
    # the calibration batch is SERVED the CPU answers
    _count_routes(parts_of)
    return cpu_out
