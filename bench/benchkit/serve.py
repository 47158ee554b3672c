"""A serving cell: open-loop traffic into ``BatchedServer`` in continuous mode.

Set-up makes the weights from the seed, builds the server at the cell's
pinned ``max_batch`` and ``capacity``, and warms every prefill width class
the window's traffic can produce, with install and decode.  The window
submits each request at its scheduled time, stamped with that time, and
calls ``step()``; a request's first token is stamped when the ``step()``
that brought it to the host returns.  Cells below capacity serve every
request of the window to completion in a bounded drain; cells above it stop
at the window's end, and the requests still queued then are not attempted.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from . import counts, reference, traffic
from .cell import Cell, program_config
from .report import Run


def width_of(n_prompt: int, capacity: int) -> int:
    """The engine's left-padded prefill width: the prompt's last
    ``capacity // 2`` tokens, rounded up to a power of two."""
    keep = min(n_prompt, max(2, capacity // 2))
    return max(2, 1 << max(0, (keep - 1).bit_length()))


def check_layout(params: Any, cfg: Any) -> None:
    """The weights drawn here have the leaves and shapes the program takes."""
    from repro.models import model as M

    want = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    got_s = jax.tree.map(lambda t: (t.shape, str(t.dtype)), params)
    want_s = jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    if got_s != want_s:
        raise SystemExit(f"weights drawn by the benchmark do not match the program's "
                         f"layout:\n{got_s}\nvs\n{want_s}")


def settings_origin(srv: Any) -> List[str]:
    """Each resolved scheduler knob and where it came from."""
    from repro.core import configstore

    entry = configstore.default_store().resolve_entry(
        configstore.context_for("serve_batching", srv.workload))
    origin = "declared defaults"
    if entry is not None:
        hw = entry["context"]["hardware"]
        here = configstore.hardware_fingerprint()
        origin = f"store entry tuned on {hw}" + ("" if hw == here else f", NOT this hardware ({here})")
    cur = srv.current_config()
    return [f"serve: {k}={cur[k]} from {'the cell (pinned)' if k == 'max_batch' else origin}"
            for k in ("max_batch", "admission", "prefill_chunk", "sync_interval")]


class Window:
    """Host records of one measured window."""

    def __init__(self) -> None:
        self.steps: List[Dict[str, float]] = []     # one per step(): times and counts
        self.sched: Dict[int, float] = {}           # rid -> scheduled arrival (abs)
        self.submit_late: List[float] = []          # submit time - scheduled time
        self.first: Dict[int, float] = {}           # rid -> first token on host
        self.admitted_at: Dict[int, float] = {}     # rid -> start of the admitting step()
        self.reqs: Dict[int, Any] = {}              # rid -> the engine's request
        self.n_prompt: Dict[int, int] = {}
        self.budget: Dict[int, int] = {}
        self.t0 = 0.0
        self.t1 = 0.0
        self.tokens_at_end: Dict[int, int] = {}   # rid -> tokens on host at the window's end
        self.active: Dict[int, Any] = {}
        self.queue: collections.deque = collections.deque()


def warm(srv: Any, widths: List[int], vocab: int) -> None:
    """Compile and run each prefill width class, install and decode."""
    rng = np.random.default_rng(0)
    for w in widths:
        srv.submit(rng.integers(traffic.FIRST_ID, vocab, size=w, dtype=np.int32),
                   budget=srv.sync_interval + 1)
        while srv.queue or srv.live_slots:
            srv.step()


def drive(srv: Any, reqs: List[traffic.Request], seconds: float, annotate: bool) -> Window:
    """Offer ``reqs`` open loop for ``seconds`` and step the server."""
    from jax.profiler import TraceAnnotation

    w = Window()
    queue: collections.deque = collections.deque()    # mirror of srv.queue
    active: Dict[int, Any] = {}                       # admitted, not yet finished
    nxt = 0
    w.t0 = t0 = time.perf_counter()
    end = t0 + seconds
    span = TraceAnnotation("bench.window") if annotate else None
    if span:
        span.__enter__()
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        while nxt < len(reqs) and t0 + reqs[nxt].at <= now:
            r = reqs[nxt]
            with TraceAnnotation("bench.submit"):
                rid = srv.submit(r.prompt, budget=r.budget, submitted=t0 + r.at)
            w.submit_late.append(time.perf_counter() - (t0 + r.at))
            w.sched[rid] = t0 + r.at
            w.reqs[rid], w.n_prompt[rid], w.budget[rid] = srv.queue[-1], len(r.prompt), r.budget
            queue.append(rid)
            nxt += 1
        if not (srv.queue or srv.live_slots):
            if nxt < len(reqs):
                time.sleep(max(0.0, min(t0 + reqs[nxt].at, end) - time.perf_counter()))
            continue
        _step(srv, w, queue, active)
    w.t1 = time.perf_counter()
    if span:
        span.__exit__(None, None, None)
    w.active, w.queue = active, queue
    w.tokens_at_end = {rid: len(e.tokens) for rid, e in w.reqs.items()}
    return w


def _step(srv: Any, w: Window, queue: collections.deque, active: Dict[int, Any]) -> None:
    """One ``step()``, with what it admitted, delivered and finished."""
    from jax.profiler import TraceAnnotation

    q_before = len(srv.queue)
    before = sum(len(e.tokens) for e in active.values())
    ts = time.perf_counter()
    with TraceAnnotation("bench.step"):
        finished = srv.step()
    te = time.perf_counter()
    n_adm = q_before - len(srv.queue)
    for _ in range(n_adm):
        rid = queue.popleft()
        w.admitted_at[rid] = ts
        active[rid] = w.reqs[rid]
    after = kv = 0
    keep = max(2, srv.capacity // 2)
    for rid, e in list(active.items()):
        after += len(e.tokens)
        if e.tokens and rid not in w.first:
            w.first[rid] = te
        if e.done:
            del active[rid]
        else:
            kv += min(w.n_prompt[rid], keep) + len(e.tokens)
    w.steps.append({"start": ts, "end": te, "admitted": n_adm, "live": srv.live_slots,
                    "queue": len(srv.queue), "finished": len(finished), "tokens": after - before,
                    "kv_tokens": kv})


def drain(srv: Any, w: Window, drain_s: float) -> None:
    """Serve what the window left queued or in flight, for at most ``drain_s``."""
    limit = time.perf_counter() + drain_s
    while (srv.queue or srv.live_slots) and time.perf_counter() < limit:
        _step(srv, w, w.queue, w.active)


def run(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str], out: Run) -> None:
    from repro.runtime.serve_loop import BatchedServer

    a = reference.Arch.from_file(cell.config)
    cfg = program_config(cell.config)
    dep, mix = cell.config["deployment"], cell.traffic
    params = reference.make_params(a, seed, cell.config["dtype"])
    check_layout(params, cfg)
    srv = BatchedServer(params, cfg, capacity=dep["capacity"], eos_id=-1, mode="continuous",
                        settings={"max_batch": dep["max_batch"]})
    for line in settings_origin(srv):
        out.note(line)
    reqs = traffic.requests(mix, seed, seconds, a.vocab)
    widths = sorted({width_of(len(r.prompt), dep["capacity"]) for r in reqs})
    out.note(f"serve: {len(reqs)} requests offered at {mix['arrivals']['rate_per_s']}/s; "
             f"prefill widths {widths}")
    warm(srv, widths, a.vocab)
    out.setup_done()

    below = mix["regime"] == "below_capacity"
    from .trace import capture
    if trace_dir:
        with capture(trace_dir):
            w = drive(srv, reqs, seconds, annotate=True)
    else:
        w = drive(srv, reqs, seconds, annotate=False)
    if below:
        drain(srv, w, mix["drain_s"])
    out.read_memory()
    finished = {rid: e for rid, e in w.reqs.items() if e.done}
    queued = [rid for rid, e in w.reqs.items() if e.slot < 0]
    attempted = [rid for rid in w.reqs if not (not below and rid in queued)]
    failed = []
    for rid in attempted:
        e = w.reqs[rid]
        toks = np.asarray(e.tokens, np.int64)
        bad_ids = bool((toks < 0).any() or (toks >= a.vocab).any())
        if e.done:
            bad = len(toks) != w.budget[rid]
        else:   # unfinished: at the drain's bound below capacity; in flight above it
            bad = below or len(toks) > w.budget[rid]
        if bad or bad_ids:
            failed.append(rid)
    out.note(f"serve: {len(w.reqs)} submitted in the window, {len(finished)} finished, "
             f"{len(queued)} still queued at the end (neither attempted nor failed)"
             if not below else
             f"serve: {len(w.reqs)} submitted in the window, {len(finished)} finished after "
             f"the drain, {len(w.reqs) - len(finished)} unfinished at its bound")
    if w.submit_late:
        out.note(f"serve: generator lateness p95 {np.percentile(w.submit_late, 95) * 1e3:.3f} ms, "
                 f"max {max(w.submit_late) * 1e3:.3f} ms")
    in_win = [s for s in w.steps if s["end"] <= w.t1]
    if in_win:
        live_kv = float(np.mean([s["kv_tokens"] for s in in_win])) * counts.kv_bytes_per_token(a)
        reserved = dep["max_batch"] * dep["capacity"] * counts.kv_bytes_per_token(a)
        out.note(f"serve: live KV over the window's steps, mean {live_kv:.0f} bytes, "
                 f"{100 * live_kv / reserved:.1f} % of the {reserved} reserved; weights "
                 f"{counts.weight_bytes(a)} bytes; mean occupied slots "
                 f"{np.mean([s['live'] for s in in_win]):.2f} of {dep['max_batch']}")
    out.attempted, out.failed = len(attempted), len(failed)
    out.records = {"window": w, "arch": a, "cell": cell, "seconds": seconds,
                   "capacity": dep["capacity"], "max_batch": dep["max_batch"],
                   "sync_interval": srv.sync_interval}
    out.end_to_end(serve_metrics(w, seconds, below))

    # The program's state goes before the reference runs.
    srv._caches = None
    del srv
    gc.collect()
    out.records["params"] = params
    out.records["finished"] = finished
    gaps, n_req, n_tok = served_check(params, a, finished, w, seed, mix, dep)
    out.note(f"check: {n_req} requests, {n_tok} served tokens compared with the float32 "
             f"reference")
    for name, value in gaps.items():
        if name in out.limits:
            out.check(name, value)
        else:
            out.note(f"check: {name} = {value!r} (read, not compared: no limit in this cell)")


def served_check(params: Any, a: reference.Arch, finished: Dict[int, Any], w: Window,
                 seed: int, mix: Dict[str, Any], dep: Dict[str, Any], quant: str = ""):
    """Gaps of the served tokens' logits below the reference's best, over a
    sample of finished requests drawn from the seed (the longest in it): the
    widest (``served_logit_gap``) and the mean over every compared token
    (``served_logit_gap_mean``).  Each request is replayed as the engine ran
    it: the prompt left-padded with 0 to its width, then the served tokens.
    With ``quant`` set, a control's first choices are read instead."""
    sample = sample_requests(finished, seed, mix["check_tokens"])
    pad_to = -(-(dep["capacity"] // 2 + mix["output_len"]["max"]) // 256) * 256
    every = [np.zeros(0)]
    for rid in sample:
        e = finished[rid]
        n = w.n_prompt[rid]
        W = width_of(n, dep["capacity"])
        prompt = np.asarray(e.prompt)[-min(n, W):]
        seq = np.concatenate([np.zeros(W - len(prompt), np.int32), prompt,
                              np.asarray(e.tokens[:-1], np.int32)])
        every.append(reference.served_gaps(params, a, seq, W - 1, np.asarray(e.tokens),
                                           quant=quant, pad_to=pad_to))
    g = np.concatenate(every)
    if not g.size:       # nothing finished: nothing compared, and not correct
        return {}, 0, 0
    gaps = {"served_logit_gap": float(g.max()), "served_logit_gap_mean": float(g.mean())}
    return gaps, len(sample), int(g.size)


def sample_requests(finished: Dict[int, Any], seed: int, target_tokens: int) -> List[int]:
    """Finished requests drawn from the seed, the longest served first, until
    ``target_tokens`` served tokens are in the sample."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(finished[r].tokens), -r))
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x6368])
    rest = [r for r in rng.permutation(sorted(finished)).tolist() if r != longest]
    out, n = [longest], len(finished[longest].tokens)
    for r in rest:
        if n >= target_tokens:
            break
        out.append(r)
        n += len(finished[r].tokens)
    return out


def serve_metrics(w: Window, seconds: float, below: bool) -> Dict[str, float]:
    """The end-to-end numbers of a window, from the host clock."""
    out: Dict[str, float] = {}
    in_win = [s for s in w.steps if s["end"] <= w.t1]
    out["serve_tokens_per_s"] = sum(s["tokens"] for s in in_win) / (w.t1 - w.t0)
    ttft = [w.first[r] - w.sched[r] for r in w.sched if r in w.first]
    if ttft:
        out["ttft_p95_s"] = float(np.percentile(ttft, 95))
    tpot = [(e.finished_at - w.first[r]) / (len(e.tokens) - 1) * 1e3
            for r, e in w.reqs.items() if e.done and r in w.first and len(e.tokens) > 1]
    if tpot:
        out["tpot_p95_ms"] = float(np.percentile(tpot, 95))
    return out
