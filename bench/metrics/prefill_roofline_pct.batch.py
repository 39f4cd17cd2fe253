"""Needed work of the prefill chunks run in the traced window (real widths,
earlier positions read once) at the chip's peaks, over the device time of
the prefill programs."""
from bench.serve_work import roofline_pct


def read(run):
    return roofline_pct(run, "prefill")
