"""Trajectory store + context-keyed baselines + the bench regression gate."""
import json
import multiprocessing
import textwrap

import numpy as np
import pytest

from repro.core import configstore
from repro.core.baseline import SCHEMA_VERSION, BaselineStore, BenchRecord
from repro.core.configstore import ConfigStore, Context
from repro.core.rpi import RPI


def _rec(values, metric="lat_ms", benchmark="synthetic", workload="wl0"):
    return BenchRecord.for_component(benchmark, metric, values, "comp", workload)


@pytest.fixture
def store(tmp_path):
    return BaselineStore(str(tmp_path / "trajectory.jsonl"))


# ----------------------------------------------------------- trajectory store
def test_append_and_roundtrip_with_provenance(store):
    rows = store.append([_rec([1.0, 2.0, 3.0])], quick=True, sha="abc123",
                        timestamp=42.0, run_id="r1")
    assert len(rows) == 1
    back = list(store.rows())
    assert len(back) == 1
    row = back[0]
    assert row["schema"] == SCHEMA_VERSION
    assert row["values"] == [1.0, 2.0, 3.0]
    assert row["git_sha"] == "abc123" and row["quick"] is True
    assert row["timestamp"] == 42.0 and row["run_id"] == "r1"
    # context carries the full PR-3 coordinates of this process
    ctx = row["context"]
    assert ctx["component"] == "comp" and ctx["workload"] == "wl0"
    assert ctx["hardware"] == configstore.hardware_fingerprint()
    assert ctx["sw"] == configstore.sw_fingerprint()


def test_appends_accumulate_instead_of_overwriting(store):
    for i in range(3):
        store.append([_rec([float(i)])], timestamp=float(i))
    assert len(list(store.rows())) == 3  # a trajectory, not a snapshot


def test_corrupt_and_future_schema_lines_are_skipped(store):
    store.append([_rec([1.0])])
    with open(store.path, "a") as f:
        f.write("{torn json\n")
        f.write(json.dumps({"schema": SCHEMA_VERSION + 1, "benchmark": "x"}) + "\n")
    assert len(list(store.rows())) == 1  # bad lines never brick the gate


def test_history_matches_context_metric_and_quick_flag(store):
    store.append([_rec([1.0], workload="wl0")], quick=True, timestamp=1.0)
    store.append([_rec([2.0], workload="wl0")], quick=False, timestamp=2.0)
    store.append([_rec([3.0], workload="OTHER")], quick=True, timestamp=3.0)
    store.append([_rec([4.0], metric="other_ms")], quick=True, timestamp=4.0)
    q = _rec([9.9], workload="wl0")
    assert store.baseline_values(q, quick=True) == [1.0]   # exact coordinates only
    assert store.baseline_values(q, quick=False) == [2.0]
    assert sorted(store.baseline_values(q)) == [1.0, 2.0]  # quick=None pools both


def test_history_window_keeps_most_recent_runs(store):
    for i in range(8):
        store.append([_rec([float(i)])], timestamp=float(i))
    assert store.baseline_values(_rec([0.0]), window=3) == [5.0, 6.0, 7.0]


def _child_append(path, values):
    BaselineStore(path).append([BenchRecord.for_component(
        "synthetic", "lat_ms", values, "comp", "wl0")], quick=True)


@pytest.mark.slow  # spawns a child interpreter to append
def test_trajectory_append_survives_process_boundary(store):
    proc = multiprocessing.get_context("spawn").Process(
        target=_child_append, args=(str(store.path), [5.0, 6.0]))
    proc.start()
    proc.join(120)
    assert proc.exitcode == 0
    assert store.baseline_values(_rec([0.0]), quick=True) == [5.0, 6.0]


# --------------------------------------------------------------------- gate
def _noise(seed, n=20, loc=100.0):
    return np.random.default_rng(seed).normal(loc, 3.0, n).tolist()


def test_gate_bootstraps_with_no_baseline(store):
    rep = store.check(_rec(_noise(0)))
    assert rep.verdict == "no_baseline" and rep.ok


def test_gate_passes_noise_and_fails_planted_regression(store):
    for i in range(3):  # three historical runs form the baseline distribution
        store.append([_rec(_noise(i))], quick=True, timestamp=float(i))
    ok = store.check(_rec(_noise(7)), quick=True)
    assert ok.verdict == "noise" and ok.ok
    assert ok.baseline_runs == 3 and ok.baseline_n == 60
    bad = store.check(_rec(_noise(7, loc=200.0)), quick=True)  # planted 2x
    assert bad.verdict == "regressed" and not bad.ok
    assert bad.comparison.p_value is not None and bad.comparison.p_value <= 0.05
    faster = store.check(_rec(_noise(7, loc=50.0)), quick=True)
    assert faster.verdict == "improved" and faster.ok


def test_gate_downgrades_evidence_free_verdicts(store):
    """One-shot wall clocks (n=1) can show a huge shift that the permutation
    test can never back at alpha — the CI gate must pass them as
    insufficient_data, not fail on evidence-free jitter."""
    store.append([_rec([100.0])], quick=True, timestamp=1.0)
    rep = store.check(_rec([150.0]), quick=True)  # +50% but 1v1
    assert rep.verdict == "insufficient_data" and rep.ok
    assert rep.comparison.p_value is None
    rep = store.check(_rec([50.0]), quick=True)   # unsupported "improvement" too
    assert rep.verdict == "insufficient_data" and rep.ok


def test_gate_verdict_is_reproducible(store):
    store.append([_rec(_noise(1))], quick=True)
    cur = _rec(_noise(2, loc=130.0))
    reports = [store.check(cur, quick=True) for _ in range(3)]
    assert len({r.verdict for r in reports}) == 1
    assert len({r.comparison.p_value for r in reports}) == 1


# ------------------------------------------- unified runner end-to-end (gate)
def test_runner_gate_fails_on_injected_regression(tmp_path, monkeypatch):
    from benchmarks import runner

    # The runner runs each benchmark in a child interpreter: the synthetic
    # one is a module on the child's path, its slowdown an env var.
    (tmp_path / "synthetic_bench.py").write_text(textwrap.dedent("""\
        import os
        import numpy as np
        from repro.core.baseline import BenchRecord

        def bench(quick, seed):
            rng = np.random.default_rng(seed)
            factor = float(os.environ["SYNTHETIC_FACTOR"])
            return [BenchRecord.for_component(
                "synthetic", "lat_ms", (rng.normal(100, 3, 15) * factor).tolist(),
                "comp", "wl0")]
        """))
    monkeypatch.setitem(runner.REGISTRY, "synthetic", "synthetic_bench")
    monkeypatch.setenv("SYNTHETIC_FACTOR", "1.0")
    monkeypatch.chdir(tmp_path)  # gate_report.json lands under tmp results/
    traj = str(tmp_path / "trajectory.jsonl")

    def gate(seed):
        return runner.run_and_gate(["synthetic"], quick=True, seed=seed,
                                   gate=True, tolerance=0.25, window=5,
                                   alpha=0.05, trajectory=traj, smoke=False)

    assert gate(1)["results"][0]["verdict"] == "no_baseline"  # bootstrap run
    assert gate(2)["results"][0]["verdict"] == "noise"        # jitter passes
    monkeypatch.setenv("SYNTHETIC_FACTOR", "2.0")
    rep = gate(3)
    assert rep["results"][0]["verdict"] == "regressed" and not rep["ok"]
    report = json.loads((tmp_path / "results/bench/gate_report.json").read_text())
    assert report["results"][0]["verdict"] == "regressed"


# ------------------------------------------------- promote gate + RPI rewiring
def test_promote_routes_through_comparator(tmp_path):
    store = ConfigStore(root=str(tmp_path / "cs"))
    ctx = Context("comp", "wl0", "hw0", "sw0")
    base = _noise(0)
    # A statistically significant 2x regression is rejected…
    assert not store.promote(ctx, {"k": 1}, baseline=base,
                             samples=_noise(5, loc=200.0))
    assert store.resolve(ctx) is None
    # …noise-level jitter is not, and the verdict rides in provenance.
    assert store.promote(ctx, {"k": 2}, baseline=base, samples=_noise(5))
    entry = store.resolve_entry(ctx)
    assert entry["settings"] == {"k": 2}
    assert entry["provenance"]["gate"]["verdict"] == "noise"
    # mode="max" flips the direction: higher throughput must promote.
    ctx2 = Context("comp", "wl1", "hw0", "sw0")
    assert store.promote(ctx2, {"k": 3}, baseline=base,
                         samples=_noise(5, loc=200.0), mode="max")
    # A singleton sample can never reach significance: the comparator's
    # effect-only "regressed" must not reject (jitter, not evidence).
    ctx3 = Context("comp", "wl2", "hw0", "sw0")
    assert store.promote(ctx3, {"k": 4}, baseline=base, samples=[400.0])
    gate = store.resolve_entry(ctx3)["provenance"]["gate"]
    assert gate["verdict"] == "insufficient_data" and gate["p_value"] is None


def test_rpi_bounds_from_distribution_quantiles():
    vals = _noise(0, n=200) + [1000.0]  # one wild outlier in the history
    rpi = RPI.from_samples("comp", "wl", {"lat_ms": vals}, slack=0.25)
    (b,) = rpi.bounds
    # min/max bounds would have dragged the envelope out to ~1250; quantile
    # bounds keep the ceiling near the distribution's bulk.
    assert b.high < 400.0
    assert rpi.check({"lat_ms": 100.0})
    assert not rpi.check({"lat_ms": 500.0})


def test_rpi_from_baseline_store(store):
    store.append([_rec(_noise(0))], quick=True)
    rec = _rec([0.0])
    rpi = RPI.from_baseline("comp", "wl0", store, [rec])
    (b,) = rpi.bounds
    assert b.metric == "lat_ms" and 50.0 < b.high < 200.0
    assert rpi.check({"lat_ms": 100.0})
