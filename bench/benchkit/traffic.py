"""The one generator of requests and training batches, driven by a data file.

A serving mix (``bench/traffic/<name>.json``, ``"kind": "serve"``) gives an
arrival process and the distributions of prompt and output lengths.  A run
of ``seconds`` at ``rate_per_s`` offers ``n = round(rate * seconds)``
requests whose sizes and gaps are the distributions' quantiles at
``(i + 0.5) / n``, in an order the file's ``schedule_seed`` shuffles: every
run gets the same sizes and arrivals, and the run's seed draws the token
ids.  Near capacity the order of a shuffle moves a p95 TTFT by a third, far
more than two runs of one order differ, so the order is the file's and not
the run's.  The mean offered rate is the file's.

A training job (``"kind": "train"``) gives the batch shape and the length
distribution of the documents packed into its rows.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np

__all__ = ["Request", "quantiles", "requests", "packed_batch"]

EOS = 1          # document separator in packed rows; the serve engine runs with EOS off
FIRST_ID = 2     # token ids are drawn from [FIRST_ID, vocab)


@dataclasses.dataclass(frozen=True)
class Request:
    at: float                # scheduled arrival, seconds from the window's start
    prompt: np.ndarray       # int32 token ids
    budget: int              # output tokens to serve


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The distribution's values at ``(i + 0.5) / n``, rounded and clipped."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
    elif kind == "pareto":           # heavy tail: x_min * (1 - u) ** (-1 / alpha)
        v = dist["x_min"] * (1 - u) ** (-1.0 / dist["alpha"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def gaps(arrivals: Dict[str, Any], n: int, seconds: float) -> np.ndarray:
    """Inter-arrival gaps summing to ``seconds``: gamma-distributed with the
    file's ``shape`` (1 is Poisson; below 1 is burstier), by quantiles."""
    shape = float(arrivals.get("shape", 1.0))
    u = (np.arange(n) + 0.5) / n
    if shape == 1.0:
        g = -np.log1p(-u)
    else:
        from scipy.stats import gamma  # scipy is installed with jax
        g = gamma.ppf(u, shape)
    return g * (seconds / g.sum())


def requests(mix: Dict[str, Any], seed: int, seconds: float, vocab: int) -> List[Request]:
    """The requests of one window, in arrival order."""
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    order = np.random.default_rng([int(mix["schedule_seed"]), 0x7261])
    prompts = order.permutation(quantiles(mix["prompt_len"], n))
    outputs = order.permutation(quantiles(mix["output_len"], n))
    g = order.permutation(gaps(mix["arrivals"], n, seconds))
    at = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x7261])
    return [Request(float(at[i]), rng.integers(FIRST_ID, vocab, size=int(prompts[i]),
                                               dtype=np.int32), int(outputs[i]))
            for i in range(n)]


def packed_batch(job: Dict[str, Any], seed: int, index: int, vocab: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Batch ``index`` of a packed training stream: (tokens, labels), each
    (batch, seq_len).  Documents of the job's lengths, separated by EOS, fill
    every row; a label is the next token of the stream."""
    b, s = job["batch"], job["seq_len"]
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x7472, int(index)])
    n_docs = max(1, int(math.ceil(b * s / max(1, job["doc_len"]["median"]))) * 2)
    lens = rng.permutation(quantiles(job["doc_len"], n_docs))
    stream = rng.integers(FIRST_ID, vocab, size=b * s + 1, dtype=np.int32)
    ends = np.cumsum(lens + 1) - 1
    stream[ends[ends < stream.size]] = EOS
    rows = np.lib.stride_tricks.sliding_window_view(stream, s + 1)[:: s][:b]
    toks = np.ascontiguousarray(rows[:, :s])
    labels = np.ascontiguousarray(rows[:, 1:]).astype(np.int32)
    return toks, labels
