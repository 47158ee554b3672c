"""Share of the traced window with no operation on the device, %."""
from benchkit import readers


def read(ctx):
    return readers.idle_share(ctx)
