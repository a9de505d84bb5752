"""Elle host: the share of the vertices in nontrivial SCCs that the
device screened, of ``jepsen_elle_scc_vertices_total`` over all routes;
the rest were searched unscreened on the CPU."""

from _elle import counter_by_label


def read(run):
    by_route = counter_by_label(run, "jepsen_elle_scc_vertices_total",
                                "route")
    total = sum(by_route.values())
    if not total:
        return None
    return 100.0 * by_route.get("device", 0) / total
