"""What a run prints: notes on earlier lines, the numbers compared with their
limits as the last lines of standard error, and the result as the last line
of standard output."""
from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional


class Run:
    def __init__(self, t_start: float, limits: Dict[str, float]) -> None:
        self.t_start = t_start
        self.limits = limits
        self.setup_s: Optional[float] = None
        self.attempted = 0
        self.failed = 0
        self.e2e: Dict[str, float] = {}
        self.checks: Dict[str, float] = {}
        self.device: Dict[str, Any] = {}
        self.records: Dict[str, Any] = {}
        self.memory_peak = 0

    def note(self, line: str) -> None:
        print(line, flush=True)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self.note(f"setup: {self.setup_s:.3f} s from process start to the window")

    def read_memory(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
        self.memory_peak = int(max(peaks))
        self.note(f"device: peak memory {self.memory_peak} bytes on the fullest chip")

    def end_to_end(self, values: Dict[str, float]) -> None:
        self.e2e.update(values)

    def check(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise SystemExit(f"no limit for {name!r} in this cell's limits file")
        self.checks[name] = float(value)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= self.limits[k] for k, v in self.checks.items()) \
            and self.failed == 0

    def result(self, metrics: List[Dict[str, Any]], values: Dict[str, float],
               breakdown: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "correct": self.correct,
            "attempted": self.attempted,
            "failed": self.failed,
            "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                        for m in metrics if values.get(m["name"]) is not None},
            "device": {**self.device, "memory_peak_bytes": self.memory_peak},
        }
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = {k: {"value": v, "limit": self.limits[k]} for k, v in self.checks.items()}
        return out

    def emit(self, result: Dict[str, Any]) -> None:
        for k, c in result["checks"].items():
            ok = "within" if c["value"] <= c["limit"] else "OVER"
            print(f"check {k} = {c['value']!r} {ok} limit {c['limit']!r}", file=sys.stderr,
                  flush=True)
        print(json.dumps(result), flush=True)
