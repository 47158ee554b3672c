"""Least time of the window's prefills of real prompt tokens over the prefill program's device time, %."""
from benchkit import readers


def read(ctx):
    return readers.prefill_roofline(ctx)
