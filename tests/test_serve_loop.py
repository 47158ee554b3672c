"""Continuous-batching serve engine: scheduler correctness + sync accounting.

The load-bearing property: the continuous scheduler is a pure reordering of
work — every request's greedy token stream is bit-identical to decoding it
alone, regardless of what shares the batch, which slot it lands in, when it
was admitted, or how host syncs are batched.  The reference is a plain
loop over ``M.prefill`` and ``M.decode_step`` (batch 1, one position) that
re-derives the engine's width and budget rules, so it shares no scheduler
code with the engine it checks.

No raw timing assertions (conftest deflake policy): here we assert counts,
identities and state-machine invariants only.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.channel import MlosChannel
from repro.core.codegen import unpack_telemetry
from repro.core.registry import get_component
from repro.core.telemetry import TelemetryEmitter
from repro.models import model as M
from repro.runtime import serve_loop, traffic
from repro.runtime.serve_loop import BatchedServer

CAPACITY = 32


def _model(name):
    cfg = get_config(name).reduced().validate()
    return M.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture(scope="module")
def served():
    return _model("olmo-1b")


# olmo-1b reduced: MHA, sequence-major cache.  StarCoder2-15B reduced: GQA
# 4/2 (head-major cache), biases, a 16-slot ring under CAPACITY 32.
@pytest.fixture(scope="module", params=["olmo-1b", "starcoder2-15b"])
def any_served(request):
    return _model(request.param)


def _prompts(n, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 250, size=int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, size=n)]


def _serve(served, settings, prompts, budget, eos_id=-1):
    params, cfg = served
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=eos_id,
                      settings=settings)
    for p in prompts:
        s.submit(p)
    metrics = s.run(max_new_tokens=budget)
    return s, metrics


def _token_streams(server):
    return {r.rid: list(r.tokens) for r in server.results.values()}


def _sequential(served, prompts, budget, eos_id=-1):
    """Each prompt decoded alone: keep the last CAPACITY // 2 tokens,
    left-pad to the next power of two (at least 2), prefill, then greedy
    decode one position at a time from ``pos = width`` until ``eos_id`` or
    the budget (clipped to CAPACITY - width for a cache that must not wrap)."""
    params, cfg = served
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, t, CAPACITY)[:2])
    decode = jax.jit(lambda p, t, c, pos: M.decode_step(p, cfg, t, c, pos))
    streams = {}
    for rid, prompt in enumerate(prompts):
        keep = prompt[-(CAPACITY // 2):]
        width = max(2, 1 << (len(keep) - 1).bit_length())
        toks = np.zeros((1, width), np.int32)
        toks[0, width - len(keep):] = keep
        b = budget if cfg.window else min(budget, CAPACITY - width)
        logits, caches = prefill(params, jnp.asarray(toks))
        out = [int(jnp.argmax(logits[0]))]
        pos = width
        while len(out) < b and out[-1] != eos_id:
            logits, caches = decode(params, jnp.asarray([out[-1]], jnp.int32),
                                    caches, jnp.asarray(pos, jnp.int32))
            out.append(int(jnp.argmax(logits[0])))
            pos += 1
        streams[rid] = out
    return streams


# -------------------------------------------------------- KV read counter
def test_kv_read_share_counts_the_blocks_decode_reads():
    """``kv_read_share`` from a known slot table, by hand.  olmo-1b at 2048
    slots reads blocks of 256 tokens; 8 slots, 4 decodes a sync.  Slot 0:
    width 512, nothing emitted: it reads 513-516 tokens, 3 blocks each.
    Slot 3: width 1024, 250 emitted: 1275-1278, 5 each.  Slot 5: width 128,
    127 emitted: 256 (1 block), then 257-259 (2 each).  The rest are empty:
    (12 + 20 + 7) / (4 x 8 x 8)."""
    from repro.kernels.flash_attention.kernel import decode_block

    cfg = get_config("olmo-1b")
    srv = BatchedServer(None, cfg, capacity=2048,
                        settings={"max_batch": 8, "sync_interval": 4})
    assert decode_block(2048, cfg.n_kv_heads, cfg.hd, False) == 256
    for slot, width, emitted in ((0, 512, 0), (3, 1024, 250), (5, 128, 127)):
        srv._slot_req[slot] = serve_loop._Request(slot, np.zeros(1, np.int32), 0.0,
                                                  slot=slot, width=width,
                                                  tokens=[7] * emitted)
    assert srv._kv_read() == {"kv_read_share": (12 + 20 + 7) / (4 * 8 * 8)}


def test_kv_read_share_reads_all_of_a_wrapped_ring():
    """StarCoder2's 4096-token ring (capacity = window) reads blocks of 1024:
    a slot past the ring's end reads all 4 a decode, one at width 2048 with
    2 emitted reads 2051-2052 tokens, 3 blocks; 32 slots, 2 decodes a sync."""
    cfg = get_config("starcoder2-15b")
    srv = BatchedServer(None, cfg, capacity=4096,
                        settings={"max_batch": 32, "sync_interval": 2})
    assert cfg.cache_len(4096) == cfg.window == 4096
    for slot, width, emitted in ((1, 2048, 2100), (2, 2048, 2)):
        srv._slot_req[slot] = serve_loop._Request(slot, np.zeros(1, np.int32), 0.0,
                                                  slot=slot, width=width,
                                                  tokens=[7] * emitted)
    assert srv._kv_read() == {"kv_read_share": (2 * 4 + 2 * 3) / (2 * 32 * 4)}


# ------------------------------------------------------- scheduler identity
def test_mixed_prompt_lengths_match_sequential_reference(any_served):
    """Mixed widths across slots: engine output == each prompt decoded alone."""
    prompts = _prompts(5, seed=1)
    srv, m = _serve(any_served,
                    {"max_batch": 3, "admission": 2, "prefill_chunk": 16,
                     "sync_interval": 2}, prompts, budget=6)
    assert _token_streams(srv) == _sequential(any_served, prompts, budget=6)
    assert m["completed"] == 5 and m["queue_depth"] == 0 and m["live_slots"] == 0


def test_eos_frees_slot_midflight_and_queued_request_is_admitted(served):
    """A sequence hitting EOS frees its slot before the batch drains, and a
    queued request decodes in the reused slot with correct state."""
    prompts = _prompts(4, seed=2)
    # discover a token that actually occurs early in request 0's stream and
    # use it as the EOS id — forcing a genuine mid-flight completion
    eos = _sequential(served, prompts, budget=8)[0][2]
    srv, m = _serve(served, {"max_batch": 2, "admission": 1, "sync_interval": 1},
                    prompts, budget=8, eos_id=eos)
    assert _token_streams(srv) == _sequential(served, prompts, budget=8,
                                              eos_id=eos)
    assert m["completed"] == 4
    eos_req = srv.results[0]
    assert eos_req.tokens[-1] == eos and len(eos_req.tokens) < 8
    # with 2 slots and 4 requests, the freed slots were reused mid-flight
    assert sorted({r.slot for r in srv.results.values()}) == [0, 1]


def test_sync_interval_amortizes_host_syncs_bitidentically(served, monkeypatch):
    """The acceptance criterion: at most ONE device→host sync per
    ``sync_interval`` decode steps, with greedy output bit-identical to
    per-step sync.  Every host read funnels through serve_loop._host_fetch,
    so counting its calls counts the syncs."""
    prompts = _prompts(6, seed=3)
    calls = {"n": 0}
    real = serve_loop._host_fetch

    def counted(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(serve_loop, "_host_fetch", counted)
    base = {"max_batch": 3, "admission": 3, "prefill_chunk": 64}
    srv1, m1 = _serve(served, dict(base, sync_interval=1), prompts, budget=7)
    n1 = calls["n"]
    calls["n"] = 0
    srv5, m5 = _serve(served, dict(base, sync_interval=5), prompts, budget=7)
    n5 = calls["n"]
    assert _token_streams(srv5) == _token_streams(srv1)
    # one _host_fetch per interval, none anywhere else in the loop
    assert n1 == m1["decode_syncs"] == m1["decode_steps"]
    assert n5 == m5["decode_syncs"] == math.ceil(m5["decode_steps"] / 5)
    assert m5["decode_syncs"] < m1["decode_syncs"]


# ------------------------------------------------------------- edge cases
def test_empty_queue_run_is_a_noop(served):
    params, cfg = served
    s = BatchedServer(params, cfg, capacity=CAPACITY)
    m = s.run()
    assert m["completed"] == 0 and m["total_tokens"] == 0
    assert m["decode_steps"] == 0 and m["decode_syncs"] == 0


def test_only_the_continuous_scheduler_is_accepted(served):
    """``mode`` keeps one legal value: callers passing it still construct
    the same server, and any other scheduler name is refused."""
    params, cfg = served
    named = BatchedServer(params, cfg, capacity=CAPACITY, mode="continuous")
    plain = BatchedServer(params, cfg, capacity=CAPACITY)
    assert named.current_config() == plain.current_config()
    with pytest.raises(ValueError, match="gang"):
        BatchedServer(params, cfg, capacity=CAPACITY, mode="gang")


def test_single_request_serves_alone(served):
    prompts = _prompts(1, seed=4)
    srv, m = _serve(served, {"max_batch": 4, "sync_interval": 3}, prompts,
                    budget=5)
    assert _token_streams(srv) == _sequential(served, prompts, budget=5)
    assert m["completed"] == 1 and m["total_tokens"] == 5


def test_budget_clipped_so_full_cache_never_wraps(served):
    """Non-windowed cache: width + budget must stay <= capacity."""
    prompts = [np.arange(2, 2 + 14, dtype=np.int32)]   # width buckets to 16
    srv, m = _serve(served, {"max_batch": 2}, prompts, budget=10_000)
    r = srv.results[0]
    assert len(r.tokens) == CAPACITY - 16   # eff budget = capacity - width


# ------------------------------------------------- per-run metric isolation
def test_run_metrics_cover_this_run_only(served):
    """The seed's self.results pollution bug: metrics must cover this run's
    completions, not every request the server ever served."""
    params, cfg = served
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 2})
    for p in _prompts(3, seed=5):
        s.submit(p)
    m1 = s.run(max_new_tokens=4)
    for p in _prompts(2, seed=6):
        s.submit(p)
    m2 = s.run(max_new_tokens=4)
    assert m1["completed"] == 3 and m2["completed"] == 2
    assert m2["total_tokens"] == 2 * 4
    assert len(s.results) == 5          # the registry still holds everything


# ------------------------------------------------------- prefill bucketing
def test_prefill_widths_are_pow2_bucketed(served):
    """Prompts of neighboring lengths share one pow2 prefill width class
    (one compiled prefill per class, not one per distinct length)."""
    params, cfg = served
    widths = []
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 2})
    real = s._prefill_fn

    def spy(p, toks, modal):
        widths.append(int(toks.shape[1]))
        return real(p, toks, modal)

    s._prefill_fn = spy
    for k in (5, 6, 7, 8, 12, 3):
        s.submit(np.arange(2, 2 + k, dtype=np.int32))
    s.run(max_new_tokens=3)
    assert sorted(set(widths)) == [4, 8, 16]
    assert all(w == 2 ** int(math.log2(w)) for w in widths)


# ------------------------------------------------------------- telemetry
def test_serve_telemetry_reaches_the_agent_channel(served):
    """The emitter streams the declared serve_batching metrics through
    core.telemetry — same packed schema the agent path consumes."""
    params, cfg = served
    meta = get_component("serve_batching")
    chan = MlosChannel.create(capacity=1 << 16)
    try:
        emitter = TelemetryEmitter(meta, chan)
        s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                          settings={"max_batch": 2}, emitter=emitter)
        for p in _prompts(3, seed=7):
            s.submit(p)
        m = s.run(max_new_tokens=4)
        assert emitter.dropped == 0
        payloads = []
        while True:
            b = chan.telemetry.pop()
            if b is None:
                break
            payloads.append(b)
        assert payloads, "no telemetry emitted"
        rec = unpack_telemetry(meta, payloads[-1])  # final-run record
        assert rec["tokens_per_s"] == pytest.approx(m["tokens_per_s"])
        assert rec["queue_depth"] == 0.0
        assert rec["live_slots"] == 0.0
    finally:
        chan.close()


# ------------------------------------------------------------ traffic engine
def test_traffic_generators_are_seeded_and_sorted():
    for name, gen in traffic.SCENARIOS.items():
        a, b = gen(11, n=8), gen(11, n=8)
        assert [x.at for x in a] == [x.at for x in b], name
        assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
        assert [x.at for x in a] == sorted(x.at for x in a), name
        assert all(x.budget >= 1 and len(x.prompt) >= 2 for x in a), name
    assert not np.array_equal(traffic.drifting(1, n=4)[0].prompt,
                              traffic.drifting(2, n=4)[0].prompt)


def test_open_loop_replay_backdates_queueing_delay(served):
    """Paced replay stamps requests with their SCHEDULED arrival, so server
    backlog shows up as latency; the drain path serves everything."""
    params, cfg = served
    arr = traffic.drifting(13, n=6, long_budget=8)
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 2})
    m = traffic.replay(s, arr, speed=50.0)
    assert m["completed"] == 6
    assert all(r.finished_at > r.submitted for r in s.results.values())


# ------------------------------------------------------------- hot swapping
def test_hot_swap_mid_run_preserves_bit_identity(any_served):
    """Online tuning's load-bearing precondition: re-knobbing the scheduler
    at sync boundaries (the only points a controller can interpose) is still
    a pure reordering — every token stream stays bit-identical to the
    sequential reference no matter how the knobs thrash mid-run."""
    prompts = _prompts(6, seed=9)
    params, cfg = any_served
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 3, "admission": 2,
                                "prefill_chunk": 16, "sync_interval": 2})
    for p in prompts:
        s.submit(p)
    s.begin_run(8)
    swaps = [{"sync_interval": 5}, {"admission": 1, "prefill_chunk": 8},
             {"sync_interval": 1, "admission": 4, "max_new_tokens": 8}]
    i = 0
    while s.queue or s.live_slots:
        s.apply_config(swaps[i % len(swaps)])
        i += 1
        s.step()
    m = s.finish_run()
    assert i >= 3, "run too short to exercise every swap"
    assert _token_streams(s) == _sequential(any_served, prompts, budget=8)
    assert m["completed"] == 6


def test_apply_config_rejects_shape_baked_knobs(served):
    params, cfg = served
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 2})
    with pytest.raises(ValueError, match="max_batch"):
        s.apply_config({"max_batch": 4})
    with pytest.raises(ValueError, match="bogus"):
        s.apply_config({"bogus": 1})
    # the declared hot-swap surface all applies cleanly and reads back
    s.apply_config({k: 3 for k in serve_loop.HOT_SWAP_KNOBS})
    got = s.current_config()
    assert all(got[k] == 3 for k in serve_loop.HOT_SWAP_KNOBS)
    assert got["max_batch"] == 2  # untouched


def test_rolling_telemetry_is_windowed_and_resets_between_runs(served):
    """Rolling records cover ONE window each — rates over the window, gauges
    point-in-time at the sync boundary — and every run starts from a clean
    window state (no leakage from the previous run's totals)."""
    params, cfg = served
    s = BatchedServer(params, cfg, capacity=CAPACITY, eos_id=-1,
                      settings={"max_batch": 2, "admission": 1,
                                "prefill_chunk": 8, "sync_interval": 2})
    for p in _prompts(4, seed=11):
        s.submit(p)
    s.begin_run(6)
    assert s.last_window is None  # nothing measured yet this run
    s.step()
    w1 = s.last_window
    assert w1 is not None and w1["tokens_per_s"] > 0
    # gauges are the instantaneous state at the boundary, not an average
    assert w1["queue_depth"] == float(len(s.queue))
    assert w1["live_slots"] == float(s.live_slots)
    s.drain()
    m1 = s.finish_run()
    assert m1["completed"] == 4

    # second run: window state must reset cleanly
    for p in _prompts(2, seed=12):
        s.submit(p)
    s.begin_run(6)
    assert s.last_window is None
    s.step()
    assert s.last_window["tokens_per_s"] > 0
    s.drain()
    assert s.finish_run()["completed"] == 2
