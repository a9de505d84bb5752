"""Kernels: the closure screen's share of its roofline.  The least
time is the least work of the SCCs the traced requests screened
(``_closure``) at the chip's bf16 peak, over the device time of the
screen executable's runs in the trace."""

from _closure import screen_ops, screen_seconds, traced_spans


def read(run):
    t = screen_seconds(run)
    if not t or not run.peaks:
        return None
    ops = screen_ops(traced_spans(run, "elle/screen"))
    if not ops:
        return None
    return 100.0 * ops / run.peaks["bf16_flops_per_s"] / t
