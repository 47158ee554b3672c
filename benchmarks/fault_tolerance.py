"""Fault-injected training: exact recovery, recovery time, checkpoint overhead.

The resilience claims of ROADMAP item 5, each measured, none asserted:

  1. **Kill → resume is exact.**  A supervisor (``chaos.respawn``) runs a
     training child that SIGKILLs itself at chaos-scheduled steps (fire-once
     journal, so a resumed run passes the kill step).  The surviving loss
     trajectory — including steps re-executed after each resume — must be
     bit-identical to an uninterrupted reference child.
  2. **Completed campaign work is never re-measured.**  A campaign child is
     killed after its first cell completes; the resumed campaign (same id)
     appends zero eval rows for any cell that finished before the kill.
  3. **Torn checkpoints degrade, not die.**  Corrupting the newest
     checkpoint makes restore fall back one step.
  4. **Async checkpointing earns its complexity.**  Train-loop blocked time
     under ``mode=async`` vs ``mode=blocking`` goes through ``stats.compare``
     — the headline verdict must be ``improved``.

Child modes (internal): ``--child train`` / ``--child campaign``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

from repro.core import stats

KILL_EXTRA_STEPS = 2   # kill steps land in [1, n_steps - KILL_EXTRA_STEPS)


# -- children -----------------------------------------------------------------
def _train_params(quick: bool) -> Dict[str, int]:
    return {"n_steps": 10 if quick else 16, "global_batch": 2, "seq_len": 32,
            "ckpt_every": 2}


def _child_train(d: Path, seed: int, quick: bool, plan_json: str) -> int:
    t_start = time.perf_counter()
    from repro.configs import get_config
    from repro.runtime.chaos import ChaosInjector, plan_from_json
    from repro.runtime.checkpoint import latest_step
    from repro.runtime.train_loop import run_training

    p = _train_params(quick)
    cfg = get_config("olmo-1b").reduced().validate()
    chaos = (ChaosInjector(plan_from_json(plan_json),
                           journal=str(d / "chaos.jsonl")) if plan_json else None)
    ckpt_dir = str(d / "ck") if chaos else None
    losses = d / ("losses_killed.jsonl" if chaos else "losses_ref.jsonl")
    resumed_from = latest_step(ckpt_dir) if ckpt_dir else None
    state = {"first": True}

    def on_step(step: int, metrics: Dict[str, float]) -> None:
        if state["first"]:
            state["first"] = False
            if resumed_from is not None:
                with open(d / "recovery.jsonl", "a") as f:
                    f.write(json.dumps({
                        "resumed_from": int(resumed_from), "first_step": step,
                        "to_first_step_s": time.perf_counter() - t_start}) + "\n")
                    f.flush()
        with open(losses, "a") as f:
            # json round-trips the float64 exactly: repr is shortest-exact
            f.write(json.dumps({"step": step, "loss": metrics["loss"]}) + "\n")
            f.flush()  # SIGKILL only loses process buffers, not OS buffers

    run_training(cfg, n_steps=p["n_steps"], global_batch=p["global_batch"],
                 seq_len=p["seq_len"], ckpt_dir=ckpt_dir,
                 ckpt_every=p["ckpt_every"], on_step=on_step, chaos=chaos,
                 seed=seed)
    return 0


def _child_campaign(d: Path, seed: int, campaign_id: str) -> int:
    from repro.core.campaign import Campaign, CampaignCell
    from repro.core import smartcomponents as _smart  # noqa: F401 — registers demo components
    from repro.launch.campaign import build_measure
    from repro.runtime.chaos import ChaosInjector, Fault

    # Uneven budgets so the short cell COMPLETES while the long one is still
    # measuring — the kill targets exactly that window.
    cells = [
        CampaignCell("hashtable", "n1024l2", "collisions", mode="min",
                     optimizer="bo", budget=2, seed=seed),
        CampaignCell("spinlock", "heavy2", "throughput_ops_s", mode="max",
                     optimizer="bo", budget=10, seed=seed),
    ]
    chaos = ChaosInjector([Fault(0, "kill")], journal=str(d / "chaos_campaign.jsonl"))
    inner = build_measure(reps=1)
    campaign = Campaign(cells, lambda c, s: inner(c, s), campaign_id=campaign_id)

    def measure(cell: CampaignCell, settings: Dict[str, Any]) -> Dict[str, float]:
        # fire the (once-only) kill as soon as some cell has fully completed
        text = (Path(campaign.journal.path).read_text()
                if Path(campaign.journal.path).exists() else "")
        if '"cell_done"' in text:
            chaos.on_step(0)
        return inner(cell, settings)

    campaign.measure = measure
    campaign.run()
    return 0


# -- parent-side pieces -------------------------------------------------------
def _spawn(mode: str, d: Path, seed: int, quick: bool, *extra: str) -> List[str]:
    argv = [sys.executable, "-m", "benchmarks.fault_tolerance",
            "--child", mode, "--dir", str(d), "--seed", str(seed)]
    if quick:
        argv.append("--quick")
    return argv + list(extra)


def _read_jsonl(path: Path) -> List[Dict[str, Any]]:
    if not path.exists():
        return []
    return [json.loads(l) for l in path.read_text().splitlines() if l.strip()]


def _kill_resume_exact(d: Path, seed: int, quick: bool) -> Dict[str, Any]:
    from repro.runtime.chaos import kills, plan_to_json, respawn

    p = _train_params(quick)
    n_kills = 2 if quick else 3
    plan = kills(seed, n_steps=p["n_steps"] - KILL_EXTRA_STEPS, n_kills=n_kills)
    restarts = respawn(_spawn("train", d, seed, quick), max_restarts=n_kills + 2)
    respawn(_spawn("train", d, seed, quick, "--no-chaos"), max_restarts=0)

    ref = {r["step"]: r["loss"] for r in _read_jsonl(d / "losses_ref.jsonl")}
    killed_rows = _read_jsonl(d / "losses_killed.jsonl")
    killed: Dict[int, float] = {}
    overlap_identical = True
    for r in killed_rows:
        s, v = r["step"], r["loss"]
        if s in killed and killed[s] != v:   # re-executed step diverged
            overlap_identical = False
        killed[s] = v
    bit_identical = (overlap_identical
                     and sorted(killed) == sorted(ref)
                     and all(killed[s] == ref[s] for s in ref))
    recovery = _read_jsonl(d / "recovery.jsonl")
    return {
        "n_steps": p["n_steps"], "kills": len(plan),
        "kill_steps": [f.at_step for f in plan], "restarts": restarts,
        "reexecuted_steps": len(killed_rows) - len(killed),
        "overlap_identical": overlap_identical, "bit_identical": bit_identical,
        "losses": [killed[s] for s in sorted(killed)],
        "recovery_s": [r["to_first_step_s"] for r in recovery],
        "plan": json.loads(plan_to_json(plan)),
    }


def _campaign_no_replay(d: Path, seed: int) -> Dict[str, Any]:
    campaign_id = f"fault-tolerance-{seed}"
    journal = Path("results/campaign") / f"{campaign_id}.jsonl"
    if journal.exists():
        journal.unlink()  # a fresh campaign, not a resume of the last bench run
    argv = _spawn("campaign", d, seed, False, "--id", campaign_id)
    first = subprocess.run(argv)
    assert first.returncode != 0, "campaign child was expected to be killed"
    rows_before = _read_jsonl(journal)
    done_before = {r["cell_id"] for r in rows_before if r["kind"] == "cell_done"}
    evals_before = sum(1 for r in rows_before if r["kind"] == "eval")
    assert done_before, "kill fired before any cell completed — bad schedule"
    second = subprocess.run(argv)
    assert second.returncode == 0, "resumed campaign did not complete"
    rows_after = _read_jsonl(journal)[len(rows_before):]
    replayed = sum(1 for r in rows_after
                   if r["kind"] == "eval" and r["cell_id"] in done_before)
    # The resumed run's campaign_start row records how many cells it
    # reconstructed from cell_done rows instead of re-running (cell-level
    # resume granularity) — completed cells are never re-journaled.
    resumed = max((int(r.get("resumed", 0)) for r in rows_after
                   if r["kind"] == "campaign_start"), default=0)
    return {
        "campaign_id": campaign_id,
        "completed_before_kill": len(done_before),
        "evals_before_kill": evals_before,
        "evals_after_kill": sum(1 for r in rows_after if r["kind"] == "eval"),
        "replayed_completed_evals": replayed,
        "cells_resumed_exactly": resumed,
    }


def _torn_fallback(d: Path, seed: int) -> Dict[str, Any]:
    import jax
    from repro.configs import get_config
    from repro.runtime.chaos import corrupt_checkpoint
    from repro.runtime.checkpoint import latest_step, restore_checkpoint
    from repro.runtime.steps import init_train_state

    ck = str(d / "ck")  # the killed run's surviving checkpoints
    newest = latest_step(ck)
    corrupt_checkpoint(ck)
    cfg = get_config("olmo-1b").reduced().validate()
    template = init_train_state(jax.random.PRNGKey(seed), cfg)
    _, manifest = restore_checkpoint(ck, template)
    return {"newest": int(newest), "restored": int(manifest["step"]),
            "fell_back": int(manifest["step"]) < int(newest)}


def _ckpt_overhead(seed: int, quick: bool) -> Dict[str, Any]:
    import tempfile

    from repro.configs import get_config
    from repro.runtime.train_loop import run_training

    cfg = get_config("olmo-1b").reduced().validate()
    reps = 6 if quick else 10
    # 3 steps of compute between saves is what the async writer overlaps
    # with; back-to-back saves would re-serialize on the wait() handoff
    samples: Dict[str, List[float]] = {"async": [], "blocking": []}
    for rep in range(reps):
        for mode in ("async", "blocking"):
            with tempfile.TemporaryDirectory() as td:
                out = run_training(cfg, n_steps=9, global_batch=2, seq_len=32,
                                   ckpt_dir=td, ckpt_every=3,
                                   ckpt_overrides={"mode": mode},
                                   seed=seed + rep)
            samples[mode].append(1000.0 * float(out["ckpt_counters"]["blocked_s"]))
    verdict = stats.compare(samples["blocking"], samples["async"],
                            mode="min", seed=seed)
    return {"async_blocked_ms": samples["async"],
            "blocking_blocked_ms": samples["blocking"],
            "saves_per_run": 3, "verdict": verdict.to_dict()}


def run(quick: bool = False, seed: int = 7) -> Dict[str, Any]:
    import tempfile

    t0 = time.time()
    res: Dict[str, Any] = {"quick": quick, "seed": seed}
    with tempfile.TemporaryDirectory() as td:
        d = Path(td)
        # every phase that spawns children runs before this process first
        # touches JAX (torn fallback, overhead): a chip has one owner
        res["train"] = _kill_resume_exact(d, seed, quick)
        res["campaign"] = _campaign_no_replay(d, seed)
        res["torn"] = _torn_fallback(d, seed)
    res["ckpt_overhead"] = _ckpt_overhead(seed, quick)
    res["wall_s"] = time.time() - t0

    tr = res["train"]
    print(f"  kill→resume: {tr['kills']} kills at steps {tr['kill_steps']}, "
          f"{tr['restarts']} restarts, re-executed {tr['reexecuted_steps']} "
          f"step(s), bit_identical={tr['bit_identical']}")
    print(f"  recovery_s: {[round(s, 2) for s in tr['recovery_s']]}")
    print(f"  torn ckpt: newest {res['torn']['newest']} → restored "
          f"{res['torn']['restored']} (fell_back={res['torn']['fell_back']})")
    ca = res["campaign"]
    print(f"  campaign: {ca['completed_before_kill']} cell(s) done pre-kill, "
          f"replayed evals for them: {ca['replayed_completed_evals']}")
    v = res["ckpt_overhead"]["verdict"]
    print(f"  async-vs-blocking blocked time: {v['verdict']} "
          f"(effect {v['effect']:+.1%}, p={v['p_value']})")

    out = Path("results/bench")
    out.mkdir(parents=True, exist_ok=True)
    (out / "fault_tolerance.json").write_text(json.dumps(res, indent=1))
    return res


def bench(quick: bool = False, seed: int = 7) -> list:
    """Unified-runner protocol: recovery-time and checkpoint-blocked-time
    sample distributions under the train_checkpoint context, with the
    exactness facts riding the records' meta."""
    from repro.core.baseline import BenchRecord
    from repro.runtime.checkpoint import workload_signature

    res = run(quick=quick, seed=seed)
    wl = workload_signature(2048)
    tr, ca = res["train"], res["campaign"]
    return [
        BenchRecord.for_component(
            "fault_tolerance", "recovery_s", tr["recovery_s"],
            "train_checkpoint", wl, mode="min", unit="s",
            kills=tr["kills"], bit_identical=tr["bit_identical"],
            replayed_completed_evals=ca["replayed_completed_evals"]),
        BenchRecord.for_component(
            "fault_tolerance", "ckpt_blocked_ms",
            res["ckpt_overhead"]["async_blocked_ms"],
            "train_checkpoint", wl, mode="min", unit="ms",
            vs_blocking=res["ckpt_overhead"]["verdict"]),
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--child", choices=("train", "campaign"), default=None)
    ap.add_argument("--dir", default=None)
    ap.add_argument("--id", default=None)
    ap.add_argument("--no-chaos", action="store_true",
                    help="(child train) uninterrupted reference run")
    args = ap.parse_args()

    if args.child == "train":
        from repro.runtime.chaos import kills, plan_to_json

        d = Path(args.dir)
        p = _train_params(args.quick)
        plan_json = ("" if args.no_chaos else plan_to_json(
            kills(args.seed, n_steps=p["n_steps"] - KILL_EXTRA_STEPS,
                  n_kills=2 if args.quick else 3)))
        return _child_train(d, args.seed, args.quick, plan_json)
    if args.child == "campaign":
        return _child_campaign(Path(args.dir), args.seed, args.id)

    res = run(quick=args.quick, seed=args.seed)
    ok = (res["train"]["bit_identical"]
          and res["campaign"]["replayed_completed_evals"] == 0
          and res["ckpt_overhead"]["verdict"]["verdict"] == "improved")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
