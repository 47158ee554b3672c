"""Serving loop: slot-level continuous batching with amortized host sync.

One scheduler: each of the ``max_batch`` slots carries its own device state
(current token, position, done flag, cache rows); a finished sequence frees
its slot at the next sync and a waiting request is prefilled *into* that
slot while every other slot keeps decoding.  EOS detection runs on device
inside the fused decode step, and the host reads token batches back only
every ``sync_interval`` steps — one device→host sync per interval instead
of one per token.

Scheduler contract:

  * Prompts are left-padded into a ``bucket_pow2``-bucketed width ``W`` so
    one compiled prefill serves a width class; generation starts at position
    ``W`` (rope phase shifted with the pad — established repo semantic).
  * Prompts longer than ``capacity // 2`` keep their most recent
    ``capacity // 2`` tokens, which bounds ``W <= capacity`` for any
    capacity and leaves room to generate.
  * For non-windowed families the per-request token budget is clipped to
    ``capacity - W`` (a full cache must not wrap); ring-buffered windowed
    caches wrap by design and keep their full budget.
  * ``admission`` bounds requests admitted per scheduler step and
    ``prefill_chunk`` bounds the summed prompt widths admitted per step
    (at least one request is always admitted — no livelock), so prefill
    work is chunked across steps instead of stalling decode for a convoy.
  * Greedy decode; ``eos_id < 0`` disables EOS (budget-only termination).

The admission/chunking/sync knobs are MLOS tunables resolved per workload
context — the serving-side analogue of the paper's workload-dependent
spinlock tuning; campaigns tune the scheduler itself.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compilecache import cached_jit, config_signature
from ..core.configstore import bucket_pow2
from ..core.registry import MetricSpec, tunable_component
from ..core.telemetry import span
from ..core.tunable import Int
from ..kernels.flash_attention.kernel import decode_block
from ..models import model as M
from ..models.attention import kv_head_major
from ..models.config import ModelConfig

__all__ = ["serve_settings", "ServeSettings", "BatchedServer", "workload_signature",
           "HOT_SWAP_KNOBS"]

# Tunables swappable on a LIVE server at a sync boundary (see apply_config):
# pure scheduling knobs that appear in no compiled shape and no jit context
# key.  max_batch (and capacity) are baked into every compiled artifact at
# __init__ — changing them means building a new server.
HOT_SWAP_KNOBS = ("admission", "prefill_chunk", "sync_interval", "max_new_tokens")


@tunable_component(
    name="serve_batching",
    tunables=(
        Int("max_batch", default=8, low=1, high=256, log=True),
        Int("max_new_tokens", default=32, low=1, high=4096, log=True),
        Int("admission", default=4, low=1, high=64, log=True),
        Int("prefill_chunk", default=64, low=8, high=4096, log=True),
        Int("sync_interval", default=4, low=1, high=64, log=True),
    ),
    metrics=(MetricSpec("tokens_per_s", "d"), MetricSpec("p50_latency_s", "d"),
             MetricSpec("queue_depth", "d"), MetricSpec("live_slots", "d")),
)
class ServeSettings:
    pass


serve_settings = ServeSettings()


def workload_signature(family: str, capacity: int) -> str:
    """Model family × bucketed cache capacity: the admission batch that
    maximizes tokens/s for short-context chat is not the one for long-context
    decode, so each serving deployment resolves its own batching."""
    return f"{family}_c{bucket_pow2(capacity)}"


def _host_fetch(x: Any) -> Any:
    """The ONE sanctioned device→host transfer in the serve loop.

    Every read of device values funnels through here so tests can count
    host syncs by monkeypatching this name; the continuous engine calls it
    exactly once per ``sync_interval`` decode steps."""
    return jax.device_get(x)


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: np.ndarray
    submitted: float
    budget: Optional[int] = None            # per-request token budget override
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finished_at: float = 0.0
    slot: int = -1
    width: int = 0                          # prefill width: the slot's first position
    eff_budget: int = 0                     # resolved (clipped) budget at admission


class BatchedServer:
    """Greedy-decoding batched server over a fixed batch-slot layout.

    Static shapes (batch = max_batch, cache = capacity) keep one compiled
    decode step for the whole run; empty slots decode garbage that is
    discarded.  ``settings`` pins explicit tunable values (benchmarks use it
    to fix a configuration without touching the tuned store); anything not
    pinned resolves through ``serve_settings.settings_for(workload)``.
    ``mode`` accepts only ``"continuous"``: callers that still pass it keep
    working, and anything else raises.
    ``emitter`` (a :class:`repro.core.telemetry.TelemetryEmitter` bound to
    the ``serve_batching`` meta) streams rolling tokens/s, p50 latency,
    queue depth and live slots — the agent path sees the same metrics the
    benchmark records.
    """

    def __init__(self, params: Any, cfg: ModelConfig, capacity: int = 256,
                 eos_id: int = 1, workload: Optional[str] = None,
                 mode: str = "continuous", settings: Optional[Dict[str, int]] = None,
                 emitter: Optional[Any] = None):
        if mode != "continuous":
            raise ValueError(f"unknown serve mode {mode!r}")
        self.params, self.cfg, self.capacity, self.eos_id = params, cfg, capacity, eos_id
        self.emitter = emitter
        self.workload = workload or workload_signature(cfg.family, capacity)
        s = serve_settings.settings_for(self.workload)
        o = dict(settings or {})
        self.max_batch = int(o.get("max_batch", s["max_batch"]))
        self.max_new_tokens = int(o.get("max_new_tokens", s["max_new_tokens"]))
        self.admission = int(o.get("admission", s["admission"]))
        self.prefill_chunk = int(o.get("prefill_chunk", s["prefill_chunk"]))
        self.sync_interval = int(o.get("sync_interval", s["sync_interval"]))
        # cross-attention caches must be one shape across every admitted
        # request (they share the batched cache), so the modal length is
        # fixed per server, not per prompt width
        self._enc_len = cfg.num_modal_tokens or max(2, bucket_pow2(max(1, capacity // 4)))
        sig = config_signature(cfg)
        # Context-keyed compiled steps: two servers over the same (config,
        # capacity, batch) share compiled artifacts in-process.  The KV
        # caches are donated in the decode step — each iteration rebinds
        # them, so XLA may update in place instead of copying the full
        # cache per token.  Donation rules out persistence (deserializing a
        # donating executable is a use-after-free, see cached_jit); per-token
        # cache copies cost more than one sub-second decode compile per
        # restart, so decode is the donating site.  Prefill mutates nothing
        # → persistent=True, and it retraces per pow2 width class under one
        # callable instead of per distinct prompt length.
        self._prefill_fn = cached_jit(
            lambda p, toks, modal: M.prefill(p, cfg, toks, capacity, modal),
            key="serve.prefill",
            context=(sig, self.workload, capacity),
            persistent=True)

        def _fused_step(p, tok, caches, pos, done):
            # a done row (free, or finished until the host syncs) decodes as
            # an empty row: attention reads none of its cache
            logits, caches = M.decode_step(p, cfg, tok, caches, jnp.where(done, -1, pos))
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            done = done | (nxt == eos_id)   # EOS tracking stays on device
            return nxt, caches, pos + 1, done

        self._decode = cached_jit(
            _fused_step, key="serve.decode_fused",
            context=(sig, self.workload, capacity, self.max_batch, eos_id),
            donate_argnums=(2,), persistent=False)
        self._axes = M.cache_batch_axes(cfg, self.max_batch, capacity, self._enc_len)

        def _install(big, small, slot, tok, pos, done, logits, width):
            # one fused admission write: slot-scatter the prefilled caches
            # AND the slot's (tok, pos, done) registers in a single compiled
            # call — op-by-op .at[] dispatches cost milliseconds each and
            # would dominate the scheduler at small model scale
            big = M.merge_slot(big, small, slot, self._axes)
            first = jnp.argmax(logits, -1).astype(jnp.int32)[0]
            return (big, tok.at[slot].set(first), pos.at[slot].set(width),
                    done.at[slot].set(False))

        self._install = cached_jit(
            _install, key="serve.install_slot",
            context=(sig, self.workload, capacity, self.max_batch),
            donate_argnums=(0,), persistent=False)

        self.queue: Deque[_Request] = deque()
        self.results: Dict[int, _Request] = {}
        self._next_rid = 0
        # per-slot device state; empty slots start done
        self._slot_req: List[Optional[_Request]] = [None] * self.max_batch
        self._free: List[int] = list(range(self.max_batch))
        self._caches = None                 # lazily built on first admission
        self._tok = jnp.zeros((self.max_batch,), jnp.int32)
        self._pos = jnp.zeros((self.max_batch,), jnp.int32)
        self._done = jnp.ones((self.max_batch,), bool)
        self.decode_steps = 0               # lifetime counters
        self.decode_syncs = 0
        self._begin_run(None)

    # ------------------------------------------------------------- admission
    def submit(self, prompt: np.ndarray, budget: Optional[int] = None,
               submitted: Optional[float] = None) -> int:
        """Queue a request.  ``submitted`` backdates the arrival (open-loop
        replay stamps the SCHEDULED time so queueing delay counts)."""
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(_Request(rid, np.asarray(prompt, np.int32),
                                   submitted if submitted is not None
                                   else time.perf_counter(), budget=budget))
        return rid

    def _n_live(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def live_slots(self) -> int:
        return self._n_live()

    def _width_of(self, n_prompt: int) -> int:
        keep = min(n_prompt, max(2, self.capacity // 2))
        return max(2, bucket_pow2(keep))

    @staticmethod
    def _pad_prompt(r: _Request, width: int) -> np.ndarray:
        toks = np.zeros((1, width), np.int32)
        n = min(len(r.prompt), width)
        if n:
            toks[0, -n:] = r.prompt[-n:]      # left-pad; keep the prompt tail
        return toks

    def _modal(self) -> Optional[jax.Array]:
        if self.cfg.family in ("encdec", "vlm"):
            return jnp.zeros((1, self._enc_len, self.cfg.d_model), jnp.float32)
        return None

    def _eff_budget(self, r: _Request, width: int) -> int:
        b = r.budget or self._budget_override or self.max_new_tokens
        if not self.cfg.window:
            b = min(b, self.capacity - width)  # full cache must not wrap
        return max(1, b)

    def _admit(self) -> int:
        """Prefill waiting requests into free slots; bounded per step by the
        ``admission`` count and the ``prefill_chunk`` width budget."""
        admitted, token_budget = 0, self.prefill_chunk
        while self._free and self.queue and admitted < self.admission:
            width = self._width_of(len(self.queue[0].prompt))
            if admitted and token_budget < width:
                break                        # chunk full; never starves (>=1 admitted)
            r = self.queue.popleft()
            token_budget -= width
            admitted += 1
            self._free.sort()
            slot = self._free.pop(0)
            self._prefill_into(slot, r, width)
        return admitted

    def _prefill_into(self, slot: int, r: _Request, width: int) -> None:
        wait_us = int((time.perf_counter() - r.submitted) * 1e6)
        with span("serve.prefill", rid=r.rid, n_prompt=len(r.prompt), width=width,
                  wait_us=wait_us):
            if self._caches is None:
                self._caches = M.init_cache(self.cfg, self.max_batch, self.capacity,
                                            self._enc_len)
            toks = self._pad_prompt(r, width)
            logits, small, _ = self._prefill_fn(self.params, jnp.asarray(toks),
                                                self._modal())
            # first token stays on device: it flows into the decode stream and
            # reaches the host with the next batched sync, not here
            self._caches, self._tok, self._pos, self._done = self._install(
                self._caches, small, jnp.asarray(slot, jnp.int32), self._tok,
                self._pos, self._done, logits, jnp.asarray(width, jnp.int32))
        r.slot, r.width = slot, width
        r.eff_budget = self._eff_budget(r, width)
        self._slot_req[slot] = r

    # ------------------------------------------------------- continuous loop
    def begin_run(self, max_new_tokens: Optional[int] = None) -> None:
        """Reset per-run accounting; open-loop drivers call this, then
        :meth:`submit` + :meth:`step` as traffic arrives, then
        :meth:`finish_run`."""
        self._begin_run(max_new_tokens)

    def _begin_run(self, budget_override: Optional[int]) -> None:
        self._budget_override = budget_override
        self._run_completed: List[_Request] = []
        self._run_steps = 0
        self._run_syncs = 0
        self._run_t0 = time.perf_counter()
        # windowed telemetry accounting: reset cleanly per run so the first
        # window of a new run() never inherits the previous run's clock/state
        self._win_tokens = 0
        self._win_completed: List[_Request] = []
        self._win_t0 = self._run_t0
        self.last_window: Optional[Dict[str, float]] = None

    # ------------------------------------------------------ live config swap
    def current_config(self) -> Dict[str, int]:
        """Snapshot of the scheduler knobs this server is running right now."""
        return {"max_batch": self.max_batch, "max_new_tokens": self.max_new_tokens,
                "admission": self.admission, "prefill_chunk": self.prefill_chunk,
                "sync_interval": self.sync_interval}

    def apply_config(self, settings: Dict[str, Any]) -> None:
        """Hot-swap scheduler knobs on a live server.

        Only :data:`HOT_SWAP_KNOBS` are accepted — pure scheduling knobs that
        no compiled artifact depends on, so a swap between :meth:`step` calls
        (i.e. at a sync boundary) can neither trigger a recompile nor perturb
        any request's token stream: the scheduler stays a pure reordering
        (bit-identity invariant) and :func:`_host_fetch` still runs exactly
        once per ``sync_interval`` decode steps — the interval just changes
        length.  Shape-baked knobs (``max_batch``) raise: changing them means
        building a new server.
        """
        bad = [k for k in settings if k not in HOT_SWAP_KNOBS]
        if bad:
            raise ValueError(f"not hot-swappable on a live server: {bad} "
                             f"(allowed: {list(HOT_SWAP_KNOBS)})")
        for k, v in settings.items():
            setattr(self, k, max(1, int(v)))

    def step(self) -> List[_Request]:
        """One scheduler step: admit into free slots, run ``sync_interval``
        decode steps on device, then one host sync.  Returns the requests
        that completed at this sync.

        Each phase is a span on the profiler's host timeline (``serve.step``
        around ``serve.admit`` ⊃ ``serve.prefill`` per admitted request,
        ``serve.decode`` with its ``kv_read_share``, ``serve.sync`` ⊃
        ``serve.fetch``, and ``serve.telemetry``); none wraps per-token
        work."""
        with span("serve.step", sync=self.decode_syncs):
            with span("serve.admit", queued=len(self.queue)):
                self._admit()
            if not self._n_live():
                return []
            emitted = []
            with span("serve.decode", n=self.sync_interval, **self._kv_read()):
                for _ in range(self.sync_interval):
                    # emit-input scheme: each step CONSUMES self._tok (writes its
                    # KV at pos and predicts the next), so the stream of step
                    # inputs is exactly the generated-token stream — the prefill's
                    # first token included — with zero extra host reads.
                    emitted.append(self._tok)
                    self._tok, self._caches, self._pos, self._done = self._decode(
                        self.params, self._tok, self._caches, self._pos, self._done)
                    self.decode_steps += 1
                    self._run_steps += 1
            with span("serve.sync"):
                finished = self._sync(emitted)
            with span("serve.telemetry"):
                self._emit_rolling()
            return finished

    def _kv_read(self) -> Dict[str, float]:
        """``kv_read_share`` of the coming ``sync_interval`` decodes: the
        self-attention KV blocks they read (``decode_block``), over
        ``max_batch x C / block`` a decode.  From the host's slot table
        alone: a live slot's decode at position ``width + emitted`` reads
        that many tokens plus one (all of a wrapped ring), rounded up to the
        block; an empty slot reads nothing.  A row that meets EOS between
        syncs is counted to the sync.  Empty for a model without such a
        cache, or one whose shape has no block (decode reads it whole)."""
        c = self.cfg.cache_len(self.capacity)
        blk = self.cfg.family != "ssm" and decode_block(
            c, self.cfg.n_kv_heads, self.cfg.hd, kv_head_major(self.cfg))
        if not blk:
            return {}
        n = self.sync_interval
        read = sum(-(-min(r.width + len(r.tokens) + t + 1, c) // blk)
                   for r in self._slot_req if r is not None for t in range(n))
        return {"kv_read_share": read / (n * self.max_batch * (c // blk))}

    def _sync(self, emitted: List[jax.Array]) -> List[_Request]:
        self.decode_syncs += 1
        self._run_syncs += 1
        with span("serve.fetch"):
            fetched = _host_fetch((emitted, self._done))
        toks_h, done_h = np.stack(fetched[0]), fetched[1]   # stack on host
        now = time.perf_counter()
        finished: List[_Request] = []
        for slot, r in enumerate(self._slot_req):
            if r is None:
                continue
            for t in range(toks_h.shape[0]):
                tok = int(toks_h[t, slot])
                r.tokens.append(tok)
                self._win_tokens += 1
                if tok == self.eos_id or len(r.tokens) >= r.eff_budget:
                    self._finish(r, now)
                    finished.append(r)
                    break
        if finished:
            # budget completions aren't EOS: fold them into the device done
            # vector in ONE batched write so the device view matches the
            # scheduler until the slots are reused
            mask = np.zeros((self.max_batch,), bool)
            mask[[r.slot for r in finished]] = True
            self._done = jnp.logical_or(self._done, jnp.asarray(mask))
        del done_h  # device-side done rides along for introspection/tests
        return finished

    def _finish(self, r: _Request, now: float) -> None:
        r.done = True
        r.finished_at = now
        self.results[r.rid] = r
        self._run_completed.append(r)
        self._win_completed.append(r)
        self._slot_req[r.slot] = None
        self._free.append(r.slot)

    def finish_run(self) -> Dict[str, float]:
        dt = max(time.perf_counter() - self._run_t0, 1e-9)
        m = self._metrics(self._run_completed, dt)
        if self.emitter is not None:
            self.emitter.emit({k: m[k] for k in
                               ("tokens_per_s", "p50_latency_s", "queue_depth", "live_slots")})
        return m

    def drain(self) -> None:
        """Serve everything currently queued WITHOUT resetting per-run
        accounting (open-loop replay primitive)."""
        while self.queue or self._n_live():
            self.step()

    def run(self, max_new_tokens: Optional[int] = None) -> Dict[str, float]:
        """Serve everything currently queued; returns throughput metrics
        computed over THIS run's completions only."""
        self._begin_run(max_new_tokens)
        self.drain()
        return self.finish_run()

    # -------------------------------------------------------------- metrics
    def _metrics(self, completed: List[_Request], dt: float) -> Dict[str, float]:
        total = sum(len(r.tokens) for r in completed)
        lat = [r.finished_at - r.submitted for r in completed]
        return {
            "tokens_per_s": total / dt,
            "p50_latency_s": float(np.median(lat)) if lat else 0.0,
            "p99_latency_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "total_tokens": float(total),
            "completed": float(len(completed)),
            "decode_steps": float(self._run_steps),
            "decode_syncs": float(self._run_syncs),
            "queue_depth": float(len(self.queue)),
            "live_slots": float(self._n_live()),
        }

    def _emit_rolling(self) -> None:
        """Per-window telemetry at the sync boundary.

        Rates (tokens/s, p50 latency) cover THIS window only — the tokens
        appended and requests completed since the previous sync — so the
        stream reacts to load/config changes within one interval instead of
        being flattened by a run-cumulative average.  Gauges (queue depth,
        live slots) are point-in-time reads AT the boundary, never averaged
        across the window.  ``last_window`` keeps the most recent record for
        in-process consumers (the online controller); the emitter, when
        attached, streams the same record to the agent channel.
        """
        now = time.perf_counter()
        lat = [r.finished_at - r.submitted for r in self._win_completed]
        m = {
            "tokens_per_s": self._win_tokens / max(now - self._win_t0, 1e-9),
            "p50_latency_s": float(np.median(lat)) if lat else 0.0,
            "queue_depth": float(len(self.queue)),
            "live_slots": float(self._n_live()),
        }
        self._win_tokens = 0
        self._win_completed = []
        self._win_t0 = now
        self.last_window = m
        if self.emitter is not None:
            self.emitter.emit(m)
