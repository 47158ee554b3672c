"""Forward and backward FLOPs of the train steps completed in the window over window x peak, %."""
from benchkit import readers


def read(ctx):
    return readers.train_mfu(ctx)
