"""Cold vs warm compile: the persistent compilation cache's headline number.

Every fresh process pays XLA trace+compile for its first jitted train step —
the dominant startup cost for real configs.  This benchmark spawns fresh
interpreters and measures that first-step wall time twice: COLD (persistent
cache disabled via ``REPRO_COMPILECACHE=off``) and WARM (the persistent cache
populated by an unmeasured priming child), so
"the cache makes restarts faster" is a ``stats.compare`` verdict over real
process boundaries, not a same-process artifact.

Phase two exercises the ``xla_runtime`` pseudo-component end-to-end: a
candidate flag configuration is measured through ``child_env`` re-exec, the
winner is promoted into the ConfigStore under this host's hardware
fingerprint, and the promoted entry is resolved back — tuned XLA flags
survive the process the same way tuned block sizes do.

This parent never starts a JAX backend: a chip belongs to one process, and
every child needs it.  The children report the cache directory and the
hardware/software fingerprints the parent keys its records by.

    PYTHONPATH=src python benchmarks/compile_cold_warm.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

from repro.core import configstore, stats
from repro.core.compilecache import (COMPONENT, ENV_CACHE_DIR, ENV_DISABLE,
                                     XLA_RUNTIME_SPACE, child_env,
                                     promote_xla_settings)

# Fresh-interpreter workload: the reduced olmo-1b train step (same recipe as
# the tier-1 loss-decrease test).  The child reports its first jitted step's
# wall time — trace + compile + first execute — plus the registry counters,
# the active cache directory and its fingerprints.
_CHILD = """
import json, time
import jax
from repro.configs import get_config
from repro.core.compilecache import enable_persistent_cache
from repro.core.configstore import hardware_fingerprint, sw_fingerprint
from repro.core.telemetry import compile_cache_counters
from repro.data.pipeline import PackedBatcher, SyntheticCorpus
from repro.runtime.steps import init_train_state, jit_train_step

cfg = get_config("olmo-1b").reduced().validate()
batch = jax.tree.map(jax.numpy.asarray,
                     PackedBatcher(SyntheticCorpus(cfg.vocab_size, seed=0),
                                   4, 64).batch_at(0))
state = init_train_state(jax.random.PRNGKey(0), cfg)
step = jit_train_step(cfg)
t0 = time.perf_counter()
state, metrics = step(state, batch, 1.0)
jax.block_until_ready(metrics)
dt = time.perf_counter() - t0
cache_dir = enable_persistent_cache()
print(json.dumps({"first_step_s": dt, "counters": compile_cache_counters(),
                  "cache_dir": str(cache_dir) if cache_dir else None,
                  "hardware": hardware_fingerprint(), "sw": sw_fingerprint()}))
"""


def _run_child(env: Dict[str, str]) -> Dict[str, Any]:
    env = dict(env)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"child failed: {r.stderr[-1500:]}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def _first_steps(env: Dict[str, str], reps: int) -> List[Dict[str, Any]]:
    return [_run_child(env) for _ in range(reps)]


def run(reps: int = 5, seed: int = 7, cache_root: Optional[str] = None) -> Dict[str, Any]:
    # All children share one flag configuration (the declared defaults) so the
    # cold/warm contrast isolates the compilation cache, nothing else.
    defaults = XLA_RUNTIME_SPACE.defaults()
    base = child_env(defaults)

    cold_env = dict(base)
    cold_env[ENV_DISABLE] = "off"
    cold_env.pop(ENV_CACHE_DIR, None)  # JAX itself would open a cache there
    warm_env = dict(base)
    warm_env.pop(ENV_DISABLE, None)
    if cache_root:
        warm_env[ENV_CACHE_DIR] = cache_root

    print(f"  cold: {reps} fresh interpreters, persistent cache disabled")
    cold = _first_steps(cold_env, reps)
    prime = _run_child(warm_env)
    cache_dir = prime["cache_dir"]
    print(f"  primed {cache_dir} (unmeasured)")
    print(f"  warm: {reps} fresh interpreters against the populated cache")
    warm = _first_steps(warm_env, reps)

    cold_s = [c["first_step_s"] for c in cold]
    warm_s = [w["first_step_s"] for w in warm]
    cmp = stats.compare(cold_s, warm_s, mode="min", seed=seed)
    print(f"  first step: cold {stats.median(cold_s):.2f}s → "
          f"warm {stats.median(warm_s):.2f}s ({cmp.verdict}, "
          f"effect {cmp.effect:+.0%})")

    # -- xla_runtime: measure a candidate flag config through the component's
    # own apply path (child re-exec), promote the winner, resolve it back.
    candidate = dict(defaults, eigen_multithread=False)
    cand_env = child_env(candidate, base=warm_env)
    _run_child(cand_env)  # prime: candidate flags key different executables
    cand = _first_steps(cand_env, max(reps - 2, 3))
    cand_s = [c["first_step_s"] for c in cand]
    flag_cmp = stats.compare(warm_s, cand_s, mode="min", seed=seed)
    winner, win_s, lose_s = ((candidate, cand_s, warm_s)
                             if flag_cmp.verdict == "improved"
                             else (defaults, warm_s, cand_s))
    ctx = configstore.Context(COMPONENT, configstore.WILDCARD,
                              prime["hardware"], prime["sw"])
    promoted = promote_xla_settings(
        winner, baseline=lose_s, samples=win_s, context=ctx,
        provenance={"source": "compile_cold_warm", "metric": "first_step_s",
                    "flag_verdict": flag_cmp.verdict, "seed": seed})
    configstore.invalidate_cache()
    resolved = configstore.resolve_settings(
        COMPONENT, configstore.WILDCARD, defaults=defaults,
        hardware=ctx.hardware, sw=ctx.sw)
    entry = configstore.default_store().resolve_entry(ctx)
    assert promoted, "xla_runtime promotion was gated out against its own loser"
    assert entry is not None, "no stored xla_runtime entry after promotion"
    assert {k: resolved[k] for k in winner} == dict(winner), (resolved, winner)
    print(f"  xla_runtime: candidate {flag_cmp.verdict}; promoted "
          f"{'candidate' if winner is candidate else 'defaults'} under "
          f"{entry['context']['hardware']}")

    return {
        "seed": seed, "reps": reps,
        "cold_s": cold_s, "warm_s": warm_s,
        "verdict": cmp.to_dict(),
        "cache_dir": cache_dir,
        "hardware": prime["hardware"], "sw": prime["sw"],
        "counters": warm[-1]["counters"],
        "xla_runtime": {
            "default": defaults, "candidate": candidate,
            "candidate_s": cand_s, "flag_verdict": flag_cmp.to_dict(),
            "winner": winner, "promoted": promoted, "entry": entry,
        },
    }


def _write(res: Dict[str, Any], quick: bool) -> Dict[str, Any]:
    res["quick"] = quick
    out = Path("results/bench")
    out.mkdir(parents=True, exist_ok=True)
    (out / "compile_cold_warm.json").write_text(json.dumps(res, indent=1))
    print(f"compile cold/warm OK → {out / 'compile_cold_warm.json'}")
    return res


def bench(quick: bool = False, seed: int = 7) -> List[Any]:
    """Unified-runner protocol: run + convert to baseline BenchRecords."""
    from repro.core.baseline import BenchRecord

    # 6v6 is the floor at which a clean cold/warm separation reliably clears
    # the median-permutation test at alpha=0.05; with 5v5 the test's
    # granularity leaves p hovering right at the threshold.
    res = _write(run(reps=6 if quick else 7, seed=seed), quick)
    # keyed by the children's fingerprints: this process has no backend
    ctx = configstore.Context("compilecache", "train_first_step",
                              res["hardware"], res["sw"])
    return [BenchRecord("compile_cold_warm", metric, res[key], ctx, unit="s")
            for metric, key in (("first_step_cold_s", "cold_s"),
                                ("first_step_warm_s", "warm_s"))]


def main() -> Dict[str, Any]:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="smoke budget")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--cache-root", default=None,
                    help="the warm children's JAX_COMPILATION_CACHE_DIR")
    args = ap.parse_args()
    return _write(run(reps=6 if args.quick else 7, seed=args.seed,
                      cache_root=args.cache_root), args.quick)


if __name__ == "__main__":
    main()
