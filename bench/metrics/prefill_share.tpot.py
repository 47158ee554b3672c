"""Device time of the prefill and install programs (``jit__lambda``, ``jit__install``) in the window over the window's busy time, %.

Each admitted request's prefill and the install of its cache into a slot run
between two decode programs, so every slot waits for them: this is the share
of the device's work that stands between a cell's tokens."""
from benchkit import trace as T

PREFILL_INSTALL = r"^jit__(lambda|install)$"


def share(red):
    if red is None or red["busy_s"] <= 0:
        return None
    _, seconds = T.module_seconds(red, PREFILL_INSTALL)
    return 100.0 * seconds / red["busy_s"]


def read(ctx):
    return share(ctx["trace"])
