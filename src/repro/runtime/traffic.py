"""Seeded traffic scenarios + open-loop replay for the serve engine.

ROADMAP item 1's traffic scenario engine: generators produce deterministic
arrival processes (request time, prompt tokens, output budget) from a seed,
and :func:`replay` drives a :class:`~repro.runtime.serve_loop.BatchedServer`
open-loop — arrivals land at their scheduled times whether or not the server
has kept up, so queueing delay shows up in latency instead of silently
stretching the offered load.

One mix, ``drifting``: Poisson arrivals whose output budgets flip from long
decode-heavy completions to short chat turns mid-scenario — the traffic
shift the online tuner (:mod:`repro.runtime.online`) must adapt to.

Generators live in ``runtime`` (not ``benchmarks/``) so campaign measures
can replay the same mixes without importing benchmark harnesses.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List

import numpy as np

__all__ = ["Arrival", "SCENARIOS", "drifting", "replay"]


@dataclasses.dataclass(frozen=True)
class Arrival:
    at: float               # seconds from scenario start (scheduled, open-loop)
    prompt: np.ndarray      # token ids
    budget: int             # output-token budget for this request


def _prompt(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(2, vocab, size=max(2, int(n))).astype(np.int32)


def _poisson_times(rng: np.random.Generator, n: int, rate: float) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def drifting(seed: int, n: int = 32, shift: float = 0.5, rate: float = 16.0,
             short_budget: int = 3, long_budget: int = 40,
             vocab: int = 250) -> List[Arrival]:
    """Traffic-MIX shift: the online-tuning scenario.

    Poisson arrivals whose output-budget regime flips mid-scenario: the first
    ``shift`` fraction are long decode-heavy completions (where a long sync
    interval amortizes the per-window host sync), the rest are short
    chat-style turns of a couple of tokens — under a long sync interval a
    slot that finishes early in the window burns the rest of it on wasted
    decode steps, and freed slots cannot be backfilled until the next sync
    boundary.  A config tuned for the first regime is structurally mistuned
    for the second, so a frozen server loses throughput at the shift — the
    gap online tuning must close.
    """
    rng = np.random.default_rng(seed)
    times = _poisson_times(rng, n, rate)
    k = int(np.clip(round(n * shift), 0, n))
    out = []
    for i, t in enumerate(times):
        if i < k:
            budget = int(rng.integers(max(2, 3 * long_budget // 4), long_budget + 1))
            n_prompt = int(rng.integers(4, 13))
        else:
            budget = int(rng.integers(2, short_budget + 1))
            n_prompt = int(rng.integers(3, 9))
        out.append(Arrival(float(t), _prompt(rng, n_prompt, vocab), budget))
    return out


SCENARIOS: Dict[str, Callable[..., List[Arrival]]] = {"drifting": drifting}


def replay(server, arrivals: List[Arrival], speed: float = 0.0) -> Dict[str, float]:
    """Drive ``server`` through ``arrivals`` open-loop; returns run metrics.

    ``speed`` scales scenario time onto the wall clock (2.0 = twice as fast
    as scheduled); ``speed <= 0`` disables pacing — every request is offered
    up front (a closed burst), which makes the run's timing deterministic.  Requests are stamped with their SCHEDULED
    arrival time, so queueing delay from a backed-up server counts against
    latency even though `submit` happens late.
    """
    server.begin_run()
    t0 = time.perf_counter()
    order = sorted(arrivals, key=lambda a: a.at)
    if speed <= 0.0:
        for a in order:
            server.submit(a.prompt, budget=a.budget)
        server.drain()
        return server.finish_run()
    i, n = 0, len(order)
    while i < n or server.queue or server.live_slots:
        now = (time.perf_counter() - t0) * speed
        while i < n and order[i].at <= now:
            a = order[i]
            server.submit(a.prompt, budget=a.budget,
                          submitted=t0 + a.at / speed)
            i += 1
        if not server.queue and not server.live_slots:
            if i < n:
                wait = (order[i].at - now) / speed
                time.sleep(min(max(wait, 0.0), 0.05))
            continue
        server.step()
    return server.finish_run()
