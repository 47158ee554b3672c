"""Shared test utilities — deflake policy helpers.

Suite-wide rules (ISSUE 5 deflake audit):

  * No fixed-iteration spin loops around cross-process events: waiting is
    expressed as :func:`wait_until` — a predicate plus a wall-clock deadline,
    with an optional ``tick`` callback that drives work (polling a channel,
    feeding telemetry) between checks.  Iteration counts tuned to "usually
    enough" are exactly the assertions that flake on a loaded CI box.
  * No raw timing assertions: anything comparing two durations goes through
    ``repro.core.stats`` (tolerant, noise-aware) — see tests/test_stats.py.
  * Every random draw is seeded: ``np.random.default_rng(<literal>)``,
    ``jax.random.PRNGKey(<literal>)``, or a stable digest (``zlib.crc32``)
    of the test's parameters — never ``hash()``, which is salted per process.
"""
import time


def wait_until(predicate, *, timeout_s: float = 30.0, tick=None,
               sleep_s: float = 0.002) -> bool:
    """Poll ``predicate`` until truthy or ``timeout_s`` of wall clock passes.

    ``tick()`` (when given) runs between checks to make progress — e.g.
    draining a control channel; otherwise the loop sleeps ``sleep_s``.
    Returns the predicate's final truth value so callers write
    ``assert wait_until(...)`` and get the event, not a loop count, in the
    failure message.
    """
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            return bool(predicate())
        if tick is not None:
            tick()
        else:
            time.sleep(sleep_s)
    return True


def shaped_instructions(hlo_text: str, dims, *, in_fusions: bool = True) -> list:
    """Non-bitcast instructions of an optimised HLO module whose result has
    shape ``dims``, as ``(name, opcode)``.  With ``in_fusions=False`` only
    those outside fusion bodies count: the buffers the program materialises.
    """
    import re

    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo_text))
    shape = r"\w+\[" + ",".join(str(d) for d in dims) + r"\]"
    out, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = " + shape + r"\S* ([\w\-]+)\(", line)
        if m and m.group(2) != "bitcast" and (in_fusions or comp not in fused):
            out.append((m.group(1), m.group(2)))
    return out
