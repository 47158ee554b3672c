"""95th percentile over the window's prefills of the engine's own wait from scheduled arrival to the prefill (``wait_us`` of its ``serve.prefill`` spans), ms."""
from benchkit import spans


def read(ctx):
    return spans.of(ctx, spans.admit_wait_p95_ms)
