"""Device time of the decode program per execution, from the trace, ms."""
from benchkit import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
