"""95th percentile of the wait from scheduled arrival to the start of the step() that admitted the request (host clock), ms."""
from benchkit import readers


def read(ctx):
    return readers.queue_wait_p95_ms(ctx)
