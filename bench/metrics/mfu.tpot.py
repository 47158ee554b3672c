"""Model FLOPs of the window (prefill of real prompt tokens, decode over live context) over window x peak, %."""
from benchkit import readers


def read(ctx):
    return readers.serve_mfu(ctx)
