"""Device-idle time inside the engine's ``serve.step`` spans in the window, per step, ms."""
from benchkit import spans


def read(ctx):
    return spans.of(ctx, spans.step_idle_ms)
