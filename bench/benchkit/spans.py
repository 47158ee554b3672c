"""The serve engine's own spans, read from the traced window.

The program opens ``serve.*`` spans (``repro.core.telemetry.span``, which is
``jax.profiler.TraceAnnotation``) around each phase of ``BatchedServer.step``:
``serve.step`` around ``serve.admit`` (around one ``serve.prefill`` per
admitted request, with its ``rid``, ``n_prompt``, ``width`` and ``wait_us``),
``serve.decode``, ``serve.sync`` (around ``serve.fetch``) and
``serve.telemetry``.  They land in the window's ``.xplane.pb`` beside the
device's operations, with their ids as event stats; a span's parent is the
span that contains it.  Here: those spans and the
device's idle time inside ``bench.window``, the per-layer numbers read from
them, and the notes of a traced run (idle time by the innermost span around
it, the longest idle gaps and host spans, the compiles inside the window).
A program that opens no such span gives nothing to read, and each number is
then None.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import sys
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from . import trace as T
from .cell import BENCH

PREFIX = "serve."
OUTSIDE = "outside the engine"
# the host's hand-off of a program to the TPU; a flow id (its ``_p`` stat)
# links it to the module's execution on the device (that event's ``_c``)
ENQUEUE = "DoEnqueueProgram"


class Span(NamedTuple):
    name: str
    start: float            # ns, the trace's clock
    end: float
    args: Dict[str, Any]


@dataclasses.dataclass
class Traced:
    lo: float                           # the window, ns
    hi: float
    spans: List[Span]                   # serve.* spans inside the window, by start
    idle: List[List[T.Interval]]        # per device, its idle intervals in the window
    offset: List[float] = dataclasses.field(default_factory=list)  # per device, ns (read)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def read(path: str) -> Optional[Traced]:
    """The window of a trace file, or None where it has no window or no device.

    The device's times are put on the host's clock first: a program cannot
    start before the host enqueued it, yet in the v5e's traces modules show
    up to about a millisecond before their enqueue.  Each device's times are
    shifted by the largest such lead over the flows that link its modules
    to their enqueues (:func:`clock_offset`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    window: List[T.Interval] = []
    enqueued: Dict[int, float] = {}
    devices: List[Tuple[List[T.Interval], List[Tuple[int, float]]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PREFIX) or name == T.WINDOW:
                        s = float(ev.start_ns)
                        e = s + float(ev.duration_ns)
                        if name == T.WINDOW:
                            window.append((s, e))
                        else:
                            spans.append(Span(name, s, e, dict(ev.stats)))
                    elif name == ENQUEUE:
                        flow = dict(ev.stats).get("_p")
                        if flow is not None:
                            enqueued[flow] = float(ev.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            ops: List[T.Interval] = []
            modules: List[Tuple[int, float]] = []
            for line in plane.lines:
                if line.name == T.OPS:
                    ops = [(s, e) for _, s, e in T._events(line)]
                elif line.name == T.MODULES:
                    modules = [(st["_c"], float(ev.start_ns)) for ev in line.events
                               for st in [dict(ev.stats)] if "_c" in st]
            devices.append((ops, modules))
    if not window or not devices:
        return None
    lo, hi = window[0]
    offset = [clock_offset(enqueued, modules) for _, modules in devices]
    idle = [gaps(T.union(T.clip([(s + d, e + d) for s, e in ops], lo, hi)), lo, hi)
            for (ops, _), d in zip(devices, offset)]
    return Traced(lo, hi, sorted((s for s in spans if lo <= s.start and s.end <= hi),
                                 key=lambda s: (s.start, -s.end)), idle, offset)


def clock_offset(enqueued: Dict[int, float], modules: List[Tuple[int, float]]) -> float:
    """ns to add to a device's times so that no module starts before the
    host enqueued it: the largest (enqueue − module start) over the modules
    whose flow id has an enqueue, or 0.  A lower bound of the true offset,
    short of it by the quickest enqueue-to-start latency."""
    return max([0.0] + [enqueued[flow] - start for flow, start in modules if flow in enqueued])


def gaps(busy: List[T.Interval], lo: float, hi: float) -> List[T.Interval]:
    """The complement of sorted disjoint ``busy`` intervals inside [lo, hi]."""
    out, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def overlap(a: List[T.Interval], b: List[T.Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def innermost(spans: List[Span], lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each named by the innermost span over it
    (``OUTSIDE`` where there is none).  Spans sorted by (start, -end) and
    nested, as spans of one thread are."""
    parts: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    t = lo

    def upto(x: float) -> None:
        nonlocal t
        if x > t:
            parts.append((t, x, stack[-1][1] if stack else OUTSIDE))
            t = x

    for s in spans:
        while stack and stack[-1][0] <= s.start:
            upto(stack[-1][0])
            stack.pop()
        upto(s.start)
        stack.append((s.end, s.name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return parts


def split(idle: List[T.Interval], parts: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Length of ``idle`` under each name of ``parts``."""
    out: Dict[str, float] = {}
    starts = [p[0] for p in parts]
    for s, e in idle:
        k = max(0, bisect.bisect_right(starts, s) - 1)
        while k < len(parts) and parts[k][0] < e:
            a, b, name = parts[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    return out


# ------------------------------------------------------------------ numbers
def admit_wait_p95_ms(tr: Traced) -> Optional[float]:
    """p95 of the engine's wait from scheduled arrival to the prefill."""
    waits = [s.args["wait_us"] for s in tr.named("serve.prefill") if "wait_us" in s.args]
    return float(np.percentile(waits, 95)) / 1e3 if waits else None


def prefill_pad_share(tr: Traced) -> Optional[float]:
    """Pads as a share of the prefilled width, %."""
    pre = [s.args for s in tr.named("serve.prefill") if {"width", "n_prompt"} <= set(s.args)]
    total = sum(a["width"] for a in pre)
    pads = sum(a["width"] - min(a["n_prompt"], a["width"]) for a in pre)
    return 100.0 * pads / total if total else None


def step_idle_ms(tr: Traced) -> Optional[float]:
    """Device-idle time inside ``serve.step`` spans per step, ms."""
    steps = [(s.start, s.end) for s in tr.named("serve.step")]
    if not steps:
        return None
    idle = sum(overlap(iv, steps) for iv in tr.idle) / len(tr.idle)
    return idle / len(steps) / 1e6


# ------------------------------------------------------------------ a run's trace
def trace_dirs(workload: str) -> List[str]:
    """Where ``bench/run.py`` writes the run's trace: its ``--keep-trace``,
    else ``bench/_cache/trace/<workload>``."""
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--keep-trace")
    keep = p.parse_known_args(sys.argv[1:])[0].keep_trace
    return ([keep] if keep else []) + [str(BENCH / "_cache" / "trace" / workload)]


def find(ctx) -> Optional[Traced]:
    """This run's traced window: the trace file whose window the reduced
    trace has, to the nanosecond; None where the run was not traced."""
    red = ctx["trace"]
    if red is None:
        return None
    for d in trace_dirs(ctx["cell"].name):
        try:
            tr = read(T.xplane_file(d))
        except FileNotFoundError:
            continue
        if tr is not None and (tr.hi - tr.lo) / 1e9 == red["window_s"]:
            return tr
    return None


def of(ctx, number: Callable[[Traced], Optional[float]]) -> Optional[float]:
    """``number`` of this run's window.  The first reader of a run reads the
    trace into the readers' shared context and prints the run's notes."""
    if "spans" not in ctx:
        ctx["spans"] = find(ctx)
        if ctx["spans"] is not None:
            for line in notes(ctx["spans"], ctx["rec"].get("window")):
                print(line, flush=True)
    return number(ctx["spans"]) if ctx["spans"] is not None else None


# ------------------------------------------------------------------ notes
def notes(tr: Traced, w: Any = None, top: int = 10) -> List[str]:
    """What a traced run prints: the device's idle time by the innermost
    program span around it, the longest idle gaps with theirs, the longest
    span of each name, and the compiles inside the window."""
    if not tr.spans:
        return ["spans: the program opened no serve.* span in the window"]
    parts = innermost(tr.spans, tr.lo, tr.hi)
    total: Dict[str, float] = {}
    for iv in tr.idle:
        for name, ns in split(iv, parts).items():
            total[name] = total.get(name, 0.0) + ns / len(tr.idle)
    idle_ns = sum(total.values())
    shift = "".join(f", device clock +{d / 1e6:.3f} ms" for d in tr.offset)
    out = [f"spans: device idle {idle_ns / 1e6:.3f} ms of the {(tr.hi - tr.lo) / 1e6:.3f} ms "
           f"window{shift}, by the innermost program span around it: " + ", ".join(
               f"{name} {ns / 1e6:.3f} ms ({100 * ns / idle_ns:.1f} %)"
               for name, ns in sorted(total.items(), key=lambda kv: -kv[1]))] if idle_ns else []
    longest = sorted((g for iv in tr.idle for g in iv), key=lambda g: g[0] - g[1])[:top]
    steps = tr.named("serve.step")
    starts = [st.start for st in steps]
    named = []
    for s, e in longest:
        where = split([(s, e)], parts)
        k = bisect.bisect_right(starts, s) - 1
        sync = steps[k].args.get("sync") if k >= 0 and steps[k].end >= s else None
        named.append(f"{(e - s) / 1e6:.3f} ms in {max(where, key=where.get)}"
                     + (f" (sync {sync})" if sync is not None else ""))
    out.append("spans: longest idle gaps: " + ", ".join(named))
    most: Dict[str, Span] = {}
    for s in tr.spans:
        if s.name not in most or s.end - s.start > most[s.name].end - most[s.name].start:
            most[s.name] = s
    out.append("spans: longest of each name: " + ", ".join(
        f"{n} {(s.end - s.start) / 1e6:.3f} ms" for n, s in sorted(most.items())))
    out.append(compile_note(w))
    return out


def compile_note(w: Any) -> str:
    """The program's compiles inside the window, by function."""
    try:
        from repro.core.compilecache import compiles_by_function
    except ImportError:
        return "compile: the program counts no compiles by function"
    if w is None:
        return "compile: no window to count compiles in"
    got = compiles_by_function(since=w.t0, until=w.t1)
    return (f"compile: {sum(got.values())} compiles inside the window"
            + (": " + ", ".join(f"{k} {v}" for k, v in sorted(got.items())) if got else ""))
