"""Shared arithmetic of the per-layer metric readers in ``bench/metrics/``.

Each reader gets a context: the cell, the host records of the window, the
chip's peaks and the reduced trace (None where the trace held no device
work).  A reader that finds nothing to read returns None, and the metric is
left out of the result line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from . import counts
from . import trace as T
from .cell import reader

DECODE = r"fused_step"          # jit(_fused_step): the serve engine's decode program
PREFILL = r"^jit__lambda$"      # jit(<lambda>): the serve engine's prefill program
TRAIN = r"train_step"           # jit(train_step): the compiled train step


def per_layer(cell, out, peaks, trace_dir: Optional[str]) -> Tuple[Dict[str, float], Any]:
    red = None
    if trace_dir:
        spans, devices = T.read_planes(T.xplane_file(trace_dir))
        red = T.reduce(spans, devices)
    ctx = {"cell": cell, "rec": out.records, "peaks": peaks, "trace": red}
    values = {}
    for m in cell.per_layer:
        v = reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = float(v)
    return values, red


def idle_share(ctx) -> Optional[float]:
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def host_window_s(ctx) -> float:
    w = ctx["rec"]["window"]
    return w.t1 - w.t0


# ------------------------------------------------------------------ serving
def _decode_ctx(w, rid: int, k: int, capacity: int) -> np.ndarray:
    """Live context of each decode that produced tokens 1..k-1 of a request."""
    n = min(w.n_prompt[rid], capacity // 2)
    return n + np.arange(1, k)


def serve_work(ctx) -> Dict[str, float]:
    """Model work of the window: prefills admitted in it and tokens it delivered."""
    rec = ctx["rec"]
    a, w, cap = rec["arch"], rec["window"], rec["capacity"]
    pre_f = pre_b = dec_f = kv_b = 0.0
    n_pre = n_dec = 0
    for rid, t in w.admitted_at.items():
        if t > w.t1:
            continue
        n = min(w.n_prompt[rid], cap // 2)
        pre_f += counts.prefill_flops(a, n)
        pre_b += counts.weight_bytes(a) + n * counts.kv_bytes_per_token(a)
        n_pre += 1
    per_tok = 2.0 * (counts.layer_matmul_params(a) + counts.head_params(a))
    unit = 4.0 * a.layers * a.heads * a.hd
    for rid, k in w.tokens_at_end.items():
        c = _decode_ctx(w, rid, k, cap)
        if a.window:
            c = np.minimum(c, a.window)
        dec_f += per_tok * len(c) + unit * float(c.sum())
        kv_b += counts.kv_bytes_per_token(a) * float(c.sum())
        n_dec += len(c)
    return {"prefill_flops": pre_f, "prefill_bytes": pre_b, "prefills": n_pre,
            "decode_flops": dec_f, "decode_kv_bytes": kv_b, "decode_tokens": n_dec}


def serve_mfu(ctx) -> Optional[float]:
    work = serve_work(ctx)
    total = work["prefill_flops"] + work["decode_flops"]
    if total <= 0:
        return None
    return 100.0 * total / (host_window_s(ctx) * ctx["peaks"]["bf16_flops_per_s"])


def decode_step_ms(ctx) -> Optional[float]:
    red = ctx["trace"]
    if red is None:
        return None
    n, t = T.module_seconds(red, DECODE)
    return 1e3 * t / n if n else None


def decode_roofline(ctx) -> Optional[float]:
    red = ctx["trace"]
    if red is None:
        return None
    n, t = T.module_seconds(red, DECODE)
    if not n or t <= 0:
        return None
    a, pk = ctx["rec"]["arch"], ctx["peaks"]
    work = serve_work(ctx)
    bytes_ = n * counts.weight_bytes(a) + work["decode_kv_bytes"]
    need = counts.roofline_time(work["decode_flops"], bytes_, pk["bf16_flops_per_s"],
                                pk["hbm_bytes_per_s"])
    return 100.0 * need / t


def prefill_roofline(ctx) -> Optional[float]:
    red = ctx["trace"]
    if red is None:
        return None
    n, t = T.module_seconds(red, PREFILL)
    if not n or t <= 0:
        return None
    rec, pk = ctx["rec"], ctx["peaks"]
    a, w, cap = rec["arch"], rec["window"], rec["capacity"]
    need = 0.0
    for rid, ts in w.admitted_at.items():
        if ts <= w.t1:
            m = min(w.n_prompt[rid], cap // 2)
            need += counts.roofline_time(counts.prefill_flops(a, m),
                                         counts.weight_bytes(a) + m * counts.kv_bytes_per_token(a),
                                         pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * need / t if need > 0 else None


def queue_wait_p95_ms(ctx) -> Optional[float]:
    w = ctx["rec"]["window"]
    waits = [w.admitted_at[r] - w.sched[r] for r in w.sched if r in w.admitted_at]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None


def slot_occupancy(ctx) -> Optional[float]:
    rec = ctx["rec"]
    w = rec["window"]
    steps = [s for s in w.steps if s["end"] <= w.t1]
    if not steps:
        return None
    return 100.0 * float(np.mean([(s["live"] + s["finished"]) / rec["max_batch"] for s in steps]))


# ------------------------------------------------------------------ training
def train_mfu(ctx) -> Optional[float]:
    rec = ctx["rec"]
    if not rec.get("steps_in_window"):
        return None
    flops = rec["steps_in_window"] * counts.train_step_flops(rec["arch"], rec["batch"], rec["seq"])
    return 100.0 * flops / (rec["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
