"""Mean over the window's steps of (live slots after the step + requests finished in it) / max_batch, %."""
from benchkit import readers


def read(ctx):
    return readers.slot_occupancy(ctx)
