"""Unified benchmark runner: one protocol, one record schema, one gate.

Every registered benchmark exposes ``bench(quick, seed) -> [BenchRecord]``;
this runner runs each in a child interpreter of its own, gates each record
against its stored context-keyed baseline distribution
(``repro.core.baseline``), appends the
run to ``results/bench/trajectory.jsonl`` so it becomes the next run's
baseline, and writes a machine-readable ``results/bench/gate_report.json``.

Verdicts come from the ``core.stats`` comparator: ``regressed`` requires a
statistically significant shift beyond ``--tolerance`` — noise-level jitter
passes, a planted 2x slowdown fails.  A run with no stored history reads
``no_baseline`` and passes (the gate bootstraps itself on first use).

    PYTHONPATH=src python -m benchmarks.runner --quick --gate
    PYTHONPATH=src python -m benchmarks.runner --only kernel_autotune --list
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List

from repro.core.baseline import BaselineStore, BenchRecord, TRAJECTORY_PATH

ROOT = Path(__file__).resolve().parents[1]

# name -> module exposing bench(quick, seed) -> List[BenchRecord].  Each runs
# in a child interpreter of its own, one after another: a chip belongs to one
# process, so this runner never starts a JAX backend, and a benchmark that
# spawns device-hungry children of its own finds the chip free.
REGISTRY: Dict[str, str] = {
    name: f"benchmarks.{name}" for name in (
        "optimizer_throughput", "configstore_roundtrip", "multi_instance",
        "kernel_autotune", "campaign_sweep", "compile_cold_warm",
        "serve_scenarios", "online_tuning", "fault_tolerance")
}


def run_child(module: str, *, quick: bool, seed: int) -> List[BenchRecord]:
    """Run ``module.bench(quick, seed)`` in a fresh interpreter; its records
    come back through a JSON file."""
    with tempfile.TemporaryDirectory() as td:
        out = Path(td) / "records.json"
        argv = [sys.executable, "-m", "benchmarks.runner", "--child", module,
                "--seed", str(seed), "--records-out", str(out)]
        if quick:
            argv.append("--quick")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH")) if p)
        r = subprocess.run(argv, env=env)
        if r.returncode != 0:
            raise RuntimeError(f"benchmark {module} exited {r.returncode}")
        return [BenchRecord.from_dict(d) for d in json.loads(out.read_text())]


def _child_main(module: str, *, quick: bool, seed: int, records_out: str) -> int:
    records = importlib.import_module(module).bench(quick=quick, seed=seed)
    Path(records_out).write_text(json.dumps([r.to_dict() for r in records]))
    return 0


# Post-run smoke assertions (shared with test.sh --bench-smoke and CI):
# benchmark name -> check_bench check name.
SMOKE_CHECKS = {
    "optimizer_throughput": "optimizer_throughput",
    "configstore_roundtrip": "configstore_resolve",
    "multi_instance": "multi_instance",
    "kernel_autotune": "kernel_autotune",
    "campaign_sweep": "campaign_sweep",
    "compile_cold_warm": "compile_cold_warm",
    "serve_scenarios": "serve_scenarios",
    "online_tuning": "online_tuning",
    "fault_tolerance": "fault_tolerance",
}


def run_and_gate(names: List[str], *, quick: bool, seed: int, gate: bool,
                 tolerance: float, window: int, alpha: float,
                 trajectory: str = TRAJECTORY_PATH,
                 smoke: bool = True) -> Dict[str, Any]:
    """Run benchmarks, gate against stored baselines, append the trajectory.

    Returns the gate report dict; ``report["ok"]`` is the exit verdict.
    Records are checked against history *before* this run is appended — a
    run never gates against itself.
    """
    store = BaselineStore(trajectory)
    report: Dict[str, Any] = {"quick": quick, "seed": seed,
                              "tolerance": tolerance, "window": window,
                              "alpha": alpha, "results": [], "ok": True}
    for name in names:
        print(f"\n=== {name} " + "=" * max(1, 60 - len(name)))
        records = run_child(REGISTRY[name], quick=quick, seed=seed)
        if smoke and name in SMOKE_CHECKS:
            from . import check_bench
            check_bench.run_checks([SMOKE_CHECKS[name]], expect_quick=quick or None)
        for rec in records:
            gr = store.check(rec, quick=quick, window=window,
                             tolerance=tolerance, alpha=alpha)
            report["results"].append({
                "benchmark": rec.benchmark, "metric": rec.metric,
                "context": rec.context.to_dict(), "verdict": gr.verdict,
                "baseline_runs": gr.baseline_runs,
                "comparison": gr.comparison.to_dict() if gr.comparison else None,
            })
            if gate and not gr.ok:
                report["ok"] = False
            marker = {"regressed": "✗", "improved": "▲", "noise": "·",
                      "no_baseline": "∅", "insufficient_data": "?"}[gr.verdict]
            print(f"  {marker} {gr.describe()}")
        rows = store.append(records, quick=quick)
        report.setdefault("appended", 0)
        report["appended"] += len(rows)
    out = Path("results/bench")
    out.mkdir(parents=True, exist_ok=True)
    (out / "gate_report.json").write_text(json.dumps(report, indent=1))
    print(f"\nappended {report.get('appended', 0)} records → {trajectory}; "
          f"gate report → {out / 'gate_report.json'}")
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale budgets; gates against quick baselines")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 on a statistically significant regression")
    ap.add_argument("--seed", type=int, default=7,
                    help="base seed threaded into every benchmark")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of registered benchmarks")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="min relative shift that can count as a regression")
    ap.add_argument("--alpha", type=float, default=0.05,
                    help="significance level of the permutation test")
    ap.add_argument("--window", type=int, default=5,
                    help="pool the last N stored runs as the baseline")
    ap.add_argument("--trajectory", type=str, default=TRAJECTORY_PATH)
    ap.add_argument("--no-smoke", action="store_true",
                    help="skip the check_bench smoke assertions")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--records-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        return _child_main(args.child, quick=args.quick, seed=args.seed,
                           records_out=args.records_out)

    if args.list:
        for name in REGISTRY:
            print(name)
        return 0
    names = list(REGISTRY) if args.only is None else args.only.split(",")
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        ap.error(f"unknown benchmarks {unknown}; registered: {list(REGISTRY)}")

    report = run_and_gate(names, quick=args.quick, seed=args.seed,
                          gate=args.gate, tolerance=args.tolerance,
                          window=args.window, alpha=args.alpha,
                          trajectory=args.trajectory, smoke=not args.no_smoke)
    regressed = [r for r in report["results"] if r["verdict"] == "regressed"]
    if args.gate and regressed:
        print(f"\nBENCH GATE: FAIL — {len(regressed)} significant regression(s):")
        for r in regressed:
            print(f"  ✗ {r['benchmark']}:{r['metric']} "
                  f"effect {r['comparison']['effect']:+.1%} "
                  f"p={r['comparison']['p_value']}")
        return 1
    if args.gate:
        print("\nBENCH GATE: PASS (regressions beyond tolerance: none)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
