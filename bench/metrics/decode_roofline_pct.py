"""Needed work of the decode steps run in the traced window (weights once,
each live slot's cache at its real length) at the chip's peaks, over the
device time of the decode programs."""
from bench.serve_work import roofline_pct


def read(run):
    return roofline_pct(run, "decode")
