"""Online shadow/canary tuner: state machine, rollback, journal resume.

Drives :class:`repro.runtime.online.OnlineTuner` against a deterministic fake
server whose per-window throughput is a planted function of the applied
config (plus seeded jitter so the permutation test is meaningful) — no model,
no wall clock, no timing assertions.  The serve-engine integration (bit
identity and sync accounting with the tuner's hot-swaps in the loop) lives in
``test_serve_loop.py`` where the real server fixtures are.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core.configstore import ConfigStore, context_for
from repro.core.stats import StreamingAB
from repro.runtime.online import (DEFAULT_ONLINE_KNOBS, ONLINE_SCHEMA_VERSION,
                                  OnlineJournal, OnlineTuner)
from repro.runtime.serve_loop import HOT_SWAP_KNOBS


class FakeServer:
    """Deterministic continuous-batching stand-in: one step = one window,
    whose tokens/s is a planted function of the live config."""

    workload = "fake-wl"

    def __init__(self, perf, seed: int = 0, jitter: float = 0.01):
        self.perf = perf
        self.rng = np.random.default_rng(seed)
        self.jitter = jitter
        self.cfg = {"max_batch": 8, "max_new_tokens": 32, "admission": 4,
                    "prefill_chunk": 64, "sync_interval": 4}
        self.decode_syncs = 0
        self.last_window = None
        self.queue = []
        self.live_slots = []
        self.applied = []

    def current_config(self):
        return dict(self.cfg)

    def apply_config(self, settings):
        bad = [k for k in settings if k not in HOT_SWAP_KNOBS]
        assert not bad, bad
        self.cfg.update({k: int(v) for k, v in settings.items()})
        self.applied.append(dict(settings))

    def step(self):
        self.decode_syncs += 1
        v = self.perf(self.cfg) * float(1.0 + self.jitter * self.rng.standard_normal())
        self.last_window = {"tokens_per_s": v, "p50_latency_s": 0.01,
                            "queue_depth": 0.0, "live_slots": 1.0}
        return []


def _perf_flat(base=100.0):
    return lambda cfg: base


@pytest.fixture
def store(tmp_path):
    return ConfigStore(root=str(tmp_path / "store"))


def _tuner(tmp_path, store, server, **kw):
    kw.setdefault("optimizer", "rs")
    kw.setdefault("budget", 3)
    # 6 interleaved pairs: enough for the median permutation test to reach
    # significance on a cleanly separated planted effect (4 pairs cannot —
    # the 3-1 label splits of a bimodal pool reproduce the full shift)
    kw.setdefault("windows_per_eval", 6)
    kw.setdefault("seed", 5)
    return OnlineTuner(server, store=store,
                       journal_root=str(tmp_path / "journal"), **kw)


def _run_one_canary(tuner, challenger, max_steps=64):
    """Plant ``challenger`` as the next proposal and step until its canary
    closes (verdict journaled); returns the number of steps it took."""
    tuner._next_challenger = dict(challenger)
    before = sum(1 for r in tuner.journal.rows() if r["kind"] == "canary_verdict")
    for i in range(max_steps):
        tuner.step()
        now = sum(1 for r in tuner.journal.rows() if r["kind"] == "canary_verdict")
        if now > before:
            return i + 1
    raise AssertionError("canary never closed")


# ---------------------------------------------------------------- rollback
def test_planted_regression_rolls_back_within_one_window_pair(tmp_path, store):
    # sync_interval=32 craters throughput: the canary must die on its FIRST
    # interleaved pair (effect-only fallback), not after windows_per_eval
    def perf(cfg):
        return 40.0 if cfg["sync_interval"] >= 32 else 100.0

    srv = FakeServer(perf)
    t = _tuner(tmp_path, store, srv)
    champion_before = dict(t.champion)
    steps = _run_one_canary(t, {"sync_interval": 32})
    # one A window + one B window closed it — an early abort, well under the
    # 2 * windows_per_eval steps a full canary costs
    assert steps <= 3
    assert t.rollbacks == 1 and t.promotions == 0
    assert t.champion == champion_before
    rows = t.journal.rows()
    assert [r["kind"] for r in rows][-2:] == ["canary_verdict", "rollback"]
    assert rows[-1]["reason"] == "regressed"
    assert rows[-1]["restored"] == champion_before
    assert rows[-2]["verdict"]["verdict"] == "regressed"
    # last-known-good re-applied on the server before the next window
    assert srv.applied[-1] == champion_before
    assert {k: srv.cfg[k] for k in champion_before} == champion_before


def test_challenger_only_ever_runs_on_its_b_windows(tmp_path, store):
    srv = FakeServer(_perf_flat())
    t = _tuner(tmp_path, store, srv)
    t._next_challenger = {"sync_interval": 9}
    for _ in range(2 * t.windows_per_eval + 2):
        t.step()
    # every window the challenger config was live was a B (shadow) window:
    # the applied sequence alternates champion / challenger
    seen = [a.get("sync_interval") for a in srv.applied if "sync_interval" in a]
    assert 9 in seen
    for i, v in enumerate(seen):
        if v == 9:
            assert i == 0 or seen[i - 1] != 9  # never two challenger windows in a row


# ----------------------------------------------------------------- promote
def test_improved_canary_promotes_with_live_baseline(tmp_path, store):
    def perf(cfg):
        return 200.0 if cfg["sync_interval"] == 8 else 100.0

    srv = FakeServer(perf)
    t = _tuner(tmp_path, store, srv)
    _run_one_canary(t, {"sync_interval": 8})
    assert t.promotions == 1 and t.rollbacks == 0
    assert t.champion["sync_interval"] == 8
    kinds = [r["kind"] for r in t.journal.rows()]
    assert kinds[-2:] == ["canary_verdict", "promote"]
    # the promotion went through the config store, gated against the
    # champion's live A-window samples
    entry = store.resolve_entry(context_for("serve_batching", "fake-wl"))
    assert entry is not None
    assert entry["settings"]["sync_interval"] == 8
    prov = entry["provenance"]
    assert prov["source"] == "online" and prov["tuner"] == t.tuner_id
    assert prov["gate"]["verdict"] == "improved"
    # the winner keeps serving: the server runs the new champion
    assert srv.cfg["sync_interval"] == 8


def test_noise_canary_retains_champion(tmp_path, store):
    srv = FakeServer(_perf_flat())  # challenger indistinguishable from champion
    t = _tuner(tmp_path, store, srv)
    champion_before = dict(t.champion)
    _run_one_canary(t, {"sync_interval": 8})
    assert t.promotions == 0 and t.rollbacks == 0
    assert t.champion == champion_before
    kinds = [r["kind"] for r in t.journal.rows()]
    assert kinds[-1] == "canary_verdict"
    assert t.journal.rows()[-1]["verdict"]["verdict"] == "noise"
    assert store.resolve_entry(context_for("serve_batching", "fake-wl")) is None


def test_budget_exhaustion_stops_canaries(tmp_path, store):
    srv = FakeServer(_perf_flat())
    t = _tuner(tmp_path, store, srv, budget=2)
    for _ in range(100):
        t.step()
    starts = sum(1 for r in t.journal.rows() if r["kind"] == "canary_start")
    assert starts == 2
    assert t._exhausted and t._canary is None


# ---------------------------------------------------------- window pairing
def test_window_pair_never_straddles_runs(tmp_path, store):
    srv = FakeServer(_perf_flat())
    t = _tuner(tmp_path, store, srv)
    t._next_challenger = {"sync_interval": 9}
    t.step()  # canary starts, A window measured, phase -> B
    assert t._canary is not None and t._canary["phase"] == "B"
    srv.begin_run = lambda *a, **k: None
    srv.finish_run = lambda: {}
    t.begin_run()  # new run: the dangling champion sample must be dropped
    assert t._canary["phase"] == "A"
    assert t._canary["ab"].pairs == 0


# ------------------------------------------------------------------ resume
def test_journal_resume_reconstructs_champion_and_budget(tmp_path, store):
    def perf(cfg):
        return 200.0 if cfg["sync_interval"] == 8 else 100.0

    t = _tuner(tmp_path, store, FakeServer(perf), budget=5)
    _run_one_canary(t, {"sync_interval": 8})      # promote: new champion
    _run_one_canary(t, {"sync_interval": 32})     # regresses vs it: rollback
    n_verdicts = sum(1 for r in t.journal.rows() if r["kind"] == "canary_verdict")
    assert t.champion["sync_interval"] == 8

    # "kill" the process: a fresh tuner with the same id resumes exactly
    srv2 = FakeServer(perf)
    assert srv2.cfg["sync_interval"] != 8         # fresh fake serves defaults
    t2 = _tuner(tmp_path, store, srv2, budget=5, tuner_id=t.tuner_id)
    assert t2.champion == t.champion
    assert t2._canary_seq == 2                    # numbering continues
    assert t2.core.session.budget == 5 - n_verdicts
    # resumed server immediately runs the promoted champion
    assert srv2.cfg["sync_interval"] == 8


def test_resume_rolls_back_orphaned_canary(tmp_path, store):
    srv = FakeServer(_perf_flat())
    t = _tuner(tmp_path, store, srv)
    t._next_challenger = {"sync_interval": 9}
    t.step()  # canary_start journaled, no closing row — then "killed"
    assert [r["kind"] for r in t.journal.rows()] == ["canary_start"]

    t2 = _tuner(tmp_path, store, FakeServer(_perf_flat()), tuner_id=t.tuner_id)
    rows = t2.journal.rows()
    assert [r["kind"] for r in rows] == ["canary_start", "rollback"]
    assert rows[-1]["reason"] == "resume_orphaned_canary"
    assert rows[-1]["seq"] == 1
    assert t2._canary is None


def test_future_schema_and_torn_rows_are_skipped(tmp_path, store):
    t = _tuner(tmp_path, store, FakeServer(_perf_flat()))
    t.journal.append("canary_start", seq=1, challenger={"sync_interval": 9},
                     champion=t.champion, windows=4)
    t.journal.append("canary_verdict", seq=1, challenger={"sync_interval": 9},
                     verdict={"verdict": "noise", "candidate_location": 100.0})
    with open(t.journal.path, "a") as f:
        f.write(json.dumps({"schema": ONLINE_SCHEMA_VERSION + 1,
                            "kind": "promote", "settings": {"sync_interval": 63}}) + "\n")
        f.write('{"truncated mid-wri')  # torn tail of a killed writer
    rows = t.journal.rows()
    assert len(rows) == 2  # future-schema row and torn line both skipped
    # resume neither crashes nor believes the future-schema promotion
    t2 = _tuner(tmp_path, store, FakeServer(_perf_flat()), tuner_id=t.tuner_id)
    assert t2.champion["sync_interval"] != 63
    assert t2._canary_seq == 1


# -------------------------------------------------------------- guard rails
def test_non_hot_swappable_space_is_rejected(tmp_path, store):
    from repro.core.registry import get_component
    space = get_component("serve_batching").space.subset(["max_batch"])
    with pytest.raises(ValueError, match="max_batch"):
        _tuner(tmp_path, store, FakeServer(_perf_flat()), space=space)


def test_default_space_is_the_hot_swap_slice(tmp_path, store):
    t = _tuner(tmp_path, store, FakeServer(_perf_flat()))
    assert tuple(t.space.names) == DEFAULT_ONLINE_KNOBS
    assert set(t.space.names) <= set(HOT_SWAP_KNOBS)


def test_journal_is_append_only_schema_versioned(tmp_path):
    j = OnlineJournal("t1", root=str(tmp_path / "j"))
    r1 = j.append("canary_start", seq=1)
    r2 = j.append("rollback", seq=1, reason="regressed")
    assert r1["schema"] == r2["schema"] == ONLINE_SCHEMA_VERSION
    lines = j.path.read_text().splitlines()
    assert len(lines) == 2 and all(json.loads(ln) for ln in lines)
    assert [r["kind"] for r in j.rows()] == ["canary_start", "rollback"]
