"""Smoke assertions over the benchmark JSON outputs — one importable checker.

These used to live as ``python - <<'PYEOF'`` heredocs inside ``test.sh``,
which meant three copies of the truth (test.sh, the runner, CI) and zero
tracebacks on failure.  Now ``test.sh --bench-smoke``, ``benchmarks.runner``
and the CI workflow all call the same functions, and a failing assertion
points at a real line.

    PYTHONPATH=src python -m benchmarks.check_bench optimizer_throughput \
        configstore_resolve --expect-quick
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, Optional

BENCH_DIR = Path("results/bench")


def _load(name: str, expect_quick: Optional[bool]) -> Dict[str, Any]:
    path = BENCH_DIR / f"{name}.json"
    d = json.loads(path.read_text())
    if expect_quick is not None:
        assert d.get("quick") is expect_quick, (
            f"{path}: quick={d.get('quick')!r}, expected {expect_quick}")
    return d


def check_optimizer_throughput(expect_quick: Optional[bool] = None) -> None:
    d = _load("optimizer_throughput", expect_quick)
    assert d["ask_latency_ms"], "no ask-latency points recorded"
    for n, row in d["ask_latency_ms"].items():
        assert row["numpy"] > 0 and row["jax"] > 0 and row["speedup"] > 0, (n, row)
        assert len(row["numpy_samples"]) > 0 and len(row["jax_samples"]) > 0, (n, row)
    assert d["batched"], "no batched points recorded"
    for n, row in d["batched"].items():
        assert row["sessions"] >= 2 and row["batched_ms"] > 0, (n, row)


def check_configstore_resolve(expect_quick: Optional[bool] = None) -> None:
    d = _load("configstore_resolve", expect_quick)
    assert d["fresh_process_resolution"] == "ok"
    wls = [c["workload"] for c in d["contexts"].values()]
    assert len(wls) == 2 and len(set(wls)) == 2, wls
    assert d["resolve"]["cached_ns_per_lookup"] > 0
    assert d["resolve"]["uncached_first_ms"] > 0
    assert len(d["resolve"]["cached_ns_samples"]) > 0
    assert len(d["resolve"]["uncached_ms_samples"]) >= 2


def check_kernel_autotune(expect_quick: Optional[bool] = None) -> None:
    d = _load("kernel_autotune", expect_quick)
    assert d["default_us"] > 0 and d["best_us"] > 0
    assert d["best_us"] <= d["default_us"], "tuned config slower than default"
    assert d["trace"], "no tuning trace recorded"
    assert len(d["best_samples_us"]) > 0 and len(d["default_samples_us"]) > 0


def check_campaign_sweep(expect_quick: Optional[bool] = None) -> None:
    d = _load("campaign_sweep", expect_quick)
    assert d["cells"], "no campaign cells recorded"
    assert d["warm_iters_total"] < d["cold_iters_total"], (
        f"warm-start did not beat cold: warm {d['warm_iters_total']} vs "
        f"cold {d['cold_iters_total']} total iterations-to-best")
    for cid, row in d["cells"].items():
        assert row["promoted"], f"{cid}: best config was not promoted"
        assert row["warm_source"], f"{cid}: warm cell has no transfer source"


def check_compile_cold_warm(expect_quick: Optional[bool] = None) -> None:
    d = _load("compile_cold_warm", expect_quick)
    assert len(d["cold_s"]) >= 6 and len(d["warm_s"]) >= 6, "too few samples"
    assert all(s > 0 for s in d["cold_s"] + d["warm_s"])
    v = d["verdict"]
    assert v["verdict"] == "improved", (
        f"warm restart did not beat cold compile: {v}")
    assert v["candidate_location"] < v["baseline_location"], v
    xr = d["xla_runtime"]
    assert xr["promoted"], "xla_runtime winner was not promoted"
    entry = xr["entry"]
    assert entry is not None, "no stored xla_runtime entry"
    assert entry["context"]["component"] == "xla_runtime", entry
    assert entry["context"]["hardware"], "entry not keyed by hardware fingerprint"
    assert entry["provenance"]["source"] == "compile_cold_warm", entry
    assert d["counters"]["misses"] >= 1, d["counters"]


def check_multi_instance(expect_quick: Optional[bool] = None) -> None:
    d = _load("multi_instance", expect_quick)
    assert d["instances"], "no instances recorded"
    for name, row in d["instances"].items():
        assert row["no_worse"], (
            f"{name}: multiplexed best {row['multiplexed_best']} worse than "
            f"baseline {row['baseline_best']}")


def check_online_tuning(expect_quick: Optional[bool] = None) -> None:
    d = _load("online_tuning", expect_quick)
    a = d["adapt"]
    # adaptation really happened: at least one canary won and promoted
    assert a["promotions"] >= 1, f"no canary promoted: {a}"
    kinds = a["transitions"]
    assert "canary_start" in kinds and "canary_verdict" in kinds, kinds
    assert "promote" in kinds, kinds
    # every canary that started was closed out by a verdict — except at most
    # ONE still in flight when the adapt loop stopped (a live controller is
    # snapshotted mid-canary; resume rolls such an orphan back), and an open
    # canary can only be the journal's trailing record
    open_canaries = kinds.count("canary_start") - kinds.count("canary_verdict")
    assert open_canaries in (0, 1), kinds
    if open_canaries:
        assert kinds[-1] == "canary_start", kinds
    # rollback symmetry: one rollback row per regressed/vetoed canary
    assert kinds.count("rollback") == a["rollbacks"], (kinds, a)
    assert len(d["frozen_tokens_per_s"]) >= 2, d["frozen_tokens_per_s"]
    assert all(s > 0 for s in d["frozen_tokens_per_s"] + d["tuned_tokens_per_s"]), d
    v = d["verdict"]
    assert v["verdict"] == "improved", (
        f"online tuning did not recover the traffic-mix shift: {v}")
    assert v["candidate_location"] > v["baseline_location"], v


def check_fault_tolerance(expect_quick: Optional[bool] = None) -> None:
    d = _load("fault_tolerance", expect_quick)
    tr = d["train"]
    assert tr["kills"] >= 1 and tr["restarts"] == tr["kills"], tr
    assert tr["overlap_identical"], "re-executed steps diverged from first run"
    assert tr["bit_identical"], (
        "resumed loss trajectory is not bit-identical to uninterrupted")
    assert len(tr["recovery_s"]) == tr["kills"], tr["recovery_s"]
    assert all(s > 0 for s in tr["recovery_s"]), tr["recovery_s"]
    assert d["torn"]["fell_back"], (
        f"corrupt newest checkpoint did not fall back: {d['torn']}")
    ca = d["campaign"]
    assert ca["completed_before_kill"] >= 1, ca
    assert ca["replayed_completed_evals"] == 0, (
        f"resume re-measured evals of completed cells: {ca}")
    assert ca["cells_resumed_exactly"] >= 1, ca
    v = d["ckpt_overhead"]["verdict"]
    assert v["verdict"] == "improved", (
        f"async checkpointing did not beat blocking on blocked time: {v}")
    assert v["candidate_location"] < v["baseline_location"], v


CHECKS = {
    "optimizer_throughput": check_optimizer_throughput,
    "configstore_resolve": check_configstore_resolve,
    "kernel_autotune": check_kernel_autotune,
    "multi_instance": check_multi_instance,
    "campaign_sweep": check_campaign_sweep,
    "compile_cold_warm": check_compile_cold_warm,
    "online_tuning": check_online_tuning,
    "fault_tolerance": check_fault_tolerance,
}


def run_checks(names, expect_quick: Optional[bool] = None) -> None:
    for name in names:
        CHECKS[name](expect_quick)
        print(f"bench-smoke OK: {BENCH_DIR / name}.json")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("checks", nargs="+", choices=sorted(CHECKS))
    ap.add_argument("--expect-quick", action="store_true",
                    help="assert the JSON was produced by a --quick run")
    args = ap.parse_args()
    run_checks(args.checks, expect_quick=True if args.expect_quick else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
