"""Capture a profiler trace of the measured window and reduce it to numbers.

The host spans are the benchmark's own ``TraceAnnotation``s (``bench.*``);
``bench.window`` brackets the measured window.  On each device plane the
``XLA Modules`` line holds one event per execution of a compiled program and
the ``XLA Ops`` line one per operation.  The reduction gives, inside the
window: the busy time (the union of operation intervals), each module's
device time and count, the operations that took most time, and the longest
idle gaps named by the innermost host span around them.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

WINDOW = "bench.window"
MODULES, OPS = "XLA Modules", "XLA Ops"
# ops whose event spans the ops of their body: kept for busy time, left out
# of the list of the ops that took most time
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]*$")

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Trace device activity and the ``bench.*`` host spans, without the
    Python function tracer (it would record every call of the host loop)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def xplane_file(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def module_kind(name: str) -> str:
    """The stable part of a module's name: ``jit__fused_step`` from
    ``jit__fused_step(1234)`` or ``jit__fused_step.3``."""
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", name)


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def op_name(name: str) -> str:
    """``%fusion.147`` from an op event's full HLO text."""
    return name.split(" = ", 1)[0]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(op_name(e.name), float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def read_planes(path: str) -> Tuple[List[Tuple[str, float, float]], List[Dict[str, Any]]]:
    """Host ``bench.*`` spans, and per device plane its module and op events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices: List[Dict[str, Any]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line) if ev[0].startswith("bench.")]
        elif re.match(r"^/device:TPU:\d+$", plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices.append({"name": plane.name, "modules": lines.get(MODULES, []),
                            "ops": lines.get(OPS, [])})
    return spans, devices


def reduce(spans: List[Tuple[str, float, float]], devices: List[Dict[str, Any]],
           top: int = 10) -> Optional[Dict[str, Any]]:
    """Numbers of the traced window, or None where it holds no device work."""
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win or not devices:
        return None
    lo, hi = win[0]
    window_s = (hi - lo) / 1e9
    busy, modules, ops = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        iv = union(clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        busy.append(sum(e - s for s, e in iv) / 1e9)
        prev = lo
        for s, e in iv + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for name, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                m = modules.setdefault(module_kind(name), [0, 0.0])
                m[0] += 1
                m[1] += (e - s) / 1e9
        for name, s, e in dev["ops"]:
            if s >= lo and e <= hi and not CONTAINER.match(name):
                ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
    if not any(busy):
        return None
    host = [(n, s, e) for n, s, e in spans if n != WINDOW]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    idle = []
    for s, e in longest:
        mid = (s + e) / 2
        around = [(e2 - s2, n) for n, s2, e2 in host if s2 <= mid <= e2]
        idle.append([min(around)[1] if around else "no bench span", (e - s) / 1e9])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "modules": {k: {"count": c, "seconds": t} for k, (c, t) in modules.items()},
        "device_ops": [[n, t] for n, t in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle,
        "n_devices": len(devices),
    }


def module_seconds(red: Dict[str, Any], pattern: str) -> Tuple[int, float]:
    """Executions and device seconds of the modules whose name matches."""
    n, t = 0, 0.0
    for name, m in red["modules"].items():
        if re.search(pattern, name):
            n += m["count"]
            t += m["seconds"]
    return n, t
