"""Least time of the window's decode work (weights + live-token KV bytes, or FLOPs) over the decode program's device time, %."""
from benchkit import readers


def read(ctx):
    return readers.decode_roofline(ctx)
