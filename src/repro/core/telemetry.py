"""Telemetry: app metrics + OS counters + compiled-HLO ("HW") counters.

The paper's value-add is that the developer supplies only app-level metrics
(e.g. timing of a critical section) and MLOS *automatically* gathers the
contextual OS/HW counters.  Here:

  * :func:`os_counters` reads /proc (CPU time, RSS, ctx switches, faults) —
    the OS-counter analogue on this Linux dev loop;
  * :func:`hlo_counters` extracts the TPU-world "HW counters" from a compiled
    XLA artifact — FLOPs, bytes accessed, per-device memory, and per-collective
    traffic parsed out of the optimized HLO.  On a CPU-only container these are
    the rigorous, reproducible stand-ins for silicon performance counters.

Both flow through the same :class:`TelemetryEmitter` onto the shared-memory
channel in the packed binary schema from codegen.
"""
from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional, Sequence

from .channel import MlosChannel
from .codegen import pack_telemetry
from .registry import ComponentMeta

__all__ = ["os_counters", "hlo_counters", "collective_bytes", "compile_cache_counters",
           "TelemetryEmitter", "span"]


def span(name: str, **ids: Any) -> Any:
    """Open a span on the profiler's host timeline: ``with span("serve.step",
    sync=3): ...``.  A ``jax.profiler.TraceAnnotation``, so the span lands in
    the same trace as the device's operations, on the same clock, with
    ``ids`` as its args.  The one way the program opens a span; with no
    profiler running it costs about a microsecond."""
    from jax.profiler import TraceAnnotation  # lazy: importable without a backend

    return TraceAnnotation(name, **ids)


def compile_cache_counters() -> Dict[str, float]:
    """Jit-registry telemetry (``core.compilecache``): hits, misses, live
    entries, and the compiles and compile-seconds the process has paid —
    the counters the persistent compilation cache is meant to drive toward
    zero.  Lazy import: telemetry stays importable before the backend
    initializes."""
    from .compilecache import cache_counters

    return cache_counters()

_PAGE = os.sysconf("SC_PAGE_SIZE")
_CLK = os.sysconf("SC_CLK_TCK")


class _ProcReader:
    """Open ``/proc/<pid>/{stat,status}`` once; ``seek(0)`` + read per sample.

    procfs regenerates content on read-after-rewind, so keeping the file
    objects alive turns every sample into two reads instead of two
    open/read/close round-trips (path walk + fd churn) — the difference
    between "cheap enough for inner loops" as documented and merely cheap.
    """

    __slots__ = ("stat", "status")

    def __init__(self, pid: str):
        self.stat = open(f"/proc/{pid}/stat", "rb")
        self.status = open(f"/proc/{pid}/status", "rb")

    def close(self) -> None:
        for f in (self.stat, self.status):
            try:
                f.close()
            except OSError:  # pragma: no cover
                pass


_PROC_READERS: Dict[str, _ProcReader] = {}
_PROC_READERS_PID = os.getpid()


def _proc_reader(pid: str) -> Optional[_ProcReader]:
    global _PROC_READERS_PID
    if os.getpid() != _PROC_READERS_PID:
        # fork()ed child: inherited fds are bound to the PARENT's /proc files
        # and would silently report its counters — drop and reopen.
        _PROC_READERS.clear()
        _PROC_READERS_PID = os.getpid()
    r = _PROC_READERS.get(pid)
    if r is None:
        try:
            r = _PROC_READERS[pid] = _ProcReader(pid)
        except OSError:  # pragma: no cover - /proc always present on target
            return None
    return r


def os_counters(pid: str = "self") -> Dict[str, float]:
    """CPU/memory/scheduler counters from /proc — cheap enough for inner loops."""
    out: Dict[str, float] = {}
    for _attempt in range(2):  # second pass reopens if the handles went stale
        r = _proc_reader(pid)
        if r is None:
            return out
        try:
            r.stat.seek(0)
            fields = r.stat.read().rsplit(b")", 1)[1].split()
            # fields are offset by 2 relative to proc(5) numbering after the comm strip
            out["utime_s"] = int(fields[11]) / _CLK
            out["stime_s"] = int(fields[12]) / _CLK
            out["minflt"] = float(int(fields[7]))
            out["majflt"] = float(int(fields[9]))
            out["rss_bytes"] = float(int(fields[21]) * _PAGE)
            r.status.seek(0)
            for line in r.status:
                if line.startswith(b"voluntary_ctxt_switches"):
                    out["vctx"] = float(line.split()[1])
                elif line.startswith(b"nonvoluntary_ctxt_switches"):
                    out["nvctx"] = float(line.split()[1])
            return out
        except (OSError, IndexError, ValueError):  # pragma: no cover - stale pid
            _PROC_READERS.pop(pid, None)
            r.close()
    return out


_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.MULTILINE,
)

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        n = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group("dtype"), 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result sizes of every collective op in an (optimized) HLO dump.

    ``cost_analysis()`` does not report collective traffic, so we parse the
    HLO text.  Result-shape bytes are the standard proxy for per-collective
    payload (all-gather result = full gathered tensor, etc.).  `-start/-done`
    async pairs are counted once (the `-done` carries a tuple incl. context —
    we match only `-start` for async ops by skipping `-done`).
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        out[op] = out.get(op, 0) + _shape_bytes(m.group("shape"))
    return out


def hlo_counters(compiled: Any, lowered_text: Optional[str] = None) -> Dict[str, float]:
    """FLOPs / bytes / memory / collective traffic from a compiled artifact."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["flops"] = float(ca.get("flops", 0.0))
        out["transcendentals"] = float(ca.get("transcendentals", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = float(v)
    except Exception:
        pass
    text = lowered_text
    if text is None:
        try:
            text = compiled.as_text()
        except Exception:
            text = ""
    coll = collective_bytes(text or "")
    out["collective_bytes"] = float(sum(coll.values()))
    for k, v in coll.items():
        out[f"collective_bytes[{k}]"] = float(v)
    return out


class TelemetryEmitter:
    """Binds a component instance to the channel; emits packed telemetry."""

    def __init__(self, meta: ComponentMeta, channel: MlosChannel, instance_id: int = 0):
        self.meta = meta
        self.channel = channel
        self.instance_id = instance_id
        self.dropped = 0

    def emit(self, metrics: Dict[str, Any]) -> bool:
        payload = pack_telemetry(self.meta, self.instance_id, metrics)
        ok = self.channel.telemetry.push(payload)
        if not ok:
            self.dropped += 1
        return ok

    def emit_many(self, metrics_seq: Sequence[Dict[str, Any]]) -> int:
        """Flush a batch of samples with one shared-counter round-trip
        (:meth:`ShmRing.push_many`) instead of head-read + head-publish per
        record; returns how many were accepted (the rest count as dropped)."""
        payloads: List[bytes] = [
            pack_telemetry(self.meta, self.instance_id, m) for m in metrics_seq]
        sent = self.channel.telemetry.push_many(payloads)
        self.dropped += len(payloads) - sent
        return sent
