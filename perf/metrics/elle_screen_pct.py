"""Elle host: the share of each list-append test's wall time in the
device screen of its SCCs (``elle/screen``: encode, dispatch and
settle), 100 x its spans / request wall, over the window."""

from _elle import span_pct


def read(run):
    return span_pct(run, "elle/screen")
