"""Elle host: the share of each list-append test's wall time in graph
inference (``elle/infer``: the dependency graph with its process and
realtime edges, and the anomalies that are not cycles), 100 x its
spans / request wall, over the window."""

from _elle import span_pct


def read(run):
    return span_pct(run, "elle/infer")
