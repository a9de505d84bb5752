"""Elle host: the share of each list-append test's wall time in the
CPU witness search the screens did not rule out (``elle/search``),
100 x its spans / request wall, over the window."""

from _elle import span_pct


def read(run):
    return span_pct(run, "elle/search")
