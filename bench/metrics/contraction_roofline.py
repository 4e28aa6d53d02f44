"""Least time of contraction's work (``bench/work.py``) over its device
time, in %."""


def read(ctx):
    return ctx.roofline_share("contraction")
