# mloslint: disable-file=MLOS002 -- this module IS the launch-layer tier machinery: it
# snapshots, pins, and restores raw global-tier .settings around dry-run cells so that
# everything else can stay on settings_for; reads here are save/restore, not resolution.
from ..core.compilecache import force_host_device_count

force_host_device_count(512)
# ^ MUST precede any jax import: jax locks the device count at first init.
# The 512 placeholder host devices exist ONLY for this dry-run process so
# jax.make_mesh can build the production meshes (16×16 single-pod, 2×16×16
# multi-pod); smoke tests and benchmarks see the real single CPU device.
# force_host_device_count merges into any operator-set XLA_FLAGS instead of
# clobbering them (only the device-count flag is overridden).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --all                # sweep
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
#       --shape train_4k --mesh multi --set layer_stack.remat=dots
#
# Per cell: jit(step).lower(**ShapeDtypeStructs).compile();
# memory_analysis() proves the per-chip fit, cost_analysis() + HLO collective
# parse feed §Roofline.  Results are cached under results/dryrun/ (resumable).

import argparse
import contextlib
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs import ALL_ARCHS, get_config
from ..core.telemetry import hlo_counters, os_counters
from .mesh import HW, make_production_mesh
from .shapes import SHAPES, cell_status
from .specs import build_cell, depth_units
from ..core import configstore
from ..core.optimizers import optimizer_defaults, set_optimizer_defaults
from .tuning import SINGLETONS, apply_overrides, current_settings, parse_override, split_target

# Counter-pass impl mapping: XLA cost analysis counts while-loop bodies ONCE,
# so the scanned production program undercounts FLOPs/collectives by ~the trip
# count.  The counter passes therefore lower an UNROLLED program at reduced
# depth (k=1 and k=2 repeated units) and extrapolate linearly — exact, since
# layers are homogeneous.  Each scanned impl maps to its FLOP-equivalent
# unrolled form (scan attention computes masked blocks → unrolled_full).
_COUNTER_IMPL_MAP = {
    "flash_attention": {"scan": "unrolled_full", "pallas": "unrolled", "naive": "naive",
                        "unrolled": "unrolled", "unrolled_full": "unrolled_full"},
    "ssd_kernel": {"chunked": "chunked_unrolled", "pallas": "chunked_unrolled",
                   "naive": "naive", "chunked_unrolled": "chunked_unrolled"},
}


# The production pass compiles on XLA's CPU backend, where a Pallas kernel
# cannot lower: a ``pallas`` impl compiles as its FLOP-equivalent jnp stand-in,
# and the record says so (``kernel_stand_ins``).
_CPU_STAND_IN = {"flash_attention": "unrolled", "ssd_kernel": "chunked"}


def _stand_in_overrides() -> dict:
    cur = current_settings(contexts=False)  # global-tier reads only
    return {comp: {"impl": impl} for comp, impl in _CPU_STAND_IN.items()
            if cur[comp]["impl"] == "pallas"}


@contextlib.contextmanager
def _temp_settings(overrides):
    """Scoped apply_overrides: every tier (global singleton, optimizer
    defaults, context-targeted store override) is restored on exit —
    including each singleton's explicit-set bookkeeping, so a temporary
    counter-pass override doesn't permanently pin keys against the store."""
    saved, saved_ctx, saved_opt = {}, {}, None
    store = configstore.default_store()
    for target in overrides:
        comp, workload = split_target(target)
        if workload:
            saved_ctx[(comp, workload)] = store.get_override(comp, workload)
        elif comp == "optimizer":
            saved_opt = optimizer_defaults()
        else:
            inst = SINGLETONS[comp]
            saved[comp] = (dict(inst.settings), set(getattr(inst, "_explicit_settings", ())))
    try:
        apply_overrides(overrides)
        yield
    finally:
        for k, (settings, explicit) in saved.items():
            SINGLETONS[k].settings = settings  # pre-validated snapshot
            SINGLETONS[k]._explicit_settings = explicit
        if saved_opt is not None:
            set_optimizer_defaults(**saved_opt)
        for (comp, workload), prev in saved_ctx.items():
            store.clear_override(comp, workload)
            if prev:
                store.set_override(comp, workload, prev)


def _redeploy_stored_cell_configs(workload):
    """The redeploy step of tune → validate → persist → REDEPLOY: settings
    persisted for exactly this cell context (perf.hillclimb winners) are
    applied for the cell's duration.  Keys the operator/agent explicitly set
    this process (e.g. ``--set``) are left alone.  Afterwards every singleton
    is PINNED (all keys marked explicit) for the cell: the dry-run's roofline
    attribution — counter impl remaps, the pallas HBM adjustment — assumes
    the compile runs exactly the settings recorded in ``rec['settings']``,
    so shape-keyed store entries must not silently resolve underneath it
    (context-targeted ``comp@wl`` --set overrides still outrank the pin).
    Returns (applied, undo); never raises — stale entries are skipped."""
    store = configstore.default_store()
    saved, applied = [], {}
    for comp, inst in SINGLETONS.items():
        explicit = set(getattr(inst, "_explicit_settings", ()))
        saved.append((inst, dict(inst.settings), explicit))
        try:
            entry = store.resolve_entry(configstore.context_for(comp, workload))
        except Exception as e:  # noqa: BLE001 — unreadable store ≠ dead sweep
            print(f"[configstore] skipping store for {comp}@{workload}: {e}")
            entry = None
        kv = {}
        if entry is not None and entry["context"].get("workload") == workload:
            # exact cell matches only: no cross-cell reuse here
            kv = {k: v for k, v in entry["settings"].items()
                  if k not in explicit and k in inst.settings}
        if kv:
            try:
                inst.apply_settings(kv)
                applied[comp] = kv
            except Exception as e:  # noqa: BLE001 — a stale/hand-edited entry
                # (value no longer in the tunable's domain) must not crash
                # the sweep or leave this component half-applied; skip it.
                inst.settings = dict(saved[-1][1])
                print(f"[configstore] skipping stale entry {comp}@{workload}: {e}")
        inst._explicit_settings = set(inst.settings)  # pin for the cell

    def undo():
        for inst, settings, expl in saved:
            inst.settings = settings
            inst._explicit_settings = expl

    return applied, undo


def _counter_overrides(seq_len: int) -> dict:
    cur = current_settings(contexts=False)  # global-tier reads only
    return {
        "layer_stack": {"scan_layers": False,
                        "loss_chunk": min(seq_len, 16384)},
        "flash_attention": {"impl": _COUNTER_IMPL_MAP["flash_attention"][cur["flash_attention"]["impl"]]},
        "ssd_kernel": {"impl": _COUNTER_IMPL_MAP["ssd_kernel"][cur["ssd_kernel"]["impl"]]},
    }


def _lower_compile(plan):
    jitted = jax.jit(plan.step, out_shardings=plan.out_shardings,
                     donate_argnums=plan.donate_argnums)
    lowered = jitted.lower(*plan.args)
    return lowered.compile()


def default_microbatches(arch: str, shape_name: str) -> int:
    """Grad-accumulation default: big models microbatch to bound live
    activations (an MLOS class-b tunable; the heuristic is the default)."""
    if shape_name != "train_4k":
        return 1
    return 4 if get_config(arch).param_count() > 4e10 else 1


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             microbatches: int = 0, counters: bool = True) -> dict:
    if microbatches <= 0:
        microbatches = default_microbatches(arch, shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": int(mesh.devices.size),
        "settings": current_settings(),
        "microbatches": microbatches,
        "status": "ok",
    }
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    runs, reason = cell_status(cfg, shape)
    if not runs:
        rec["status"] = "skip"
        rec["reason"] = reason
        return rec
    applied, undo = _redeploy_stored_cell_configs(f"{arch}/{shape_name}/{rec['mesh']}")
    if applied:
        rec["stored_cell_settings"] = applied
        rec["settings"] = current_settings()  # refresh: reflect the redeploy
    try:
        # ---- production pass: the deliverable compile (scanned, full depth).
        # memory_analysis proves the per-chip fit; its compile succeeding for
        # every cell IS the multi-pod dry-run requirement.
        t0 = time.perf_counter()
        stand_ins = _stand_in_overrides()
        if stand_ins:
            rec["kernel_stand_ins"] = stand_ins
        with _temp_settings(stand_ins):
            plan = build_cell(arch, shape_name, mesh, multi_pod=multi_pod,
                              microbatches=microbatches)
            rec["meta"] = dict(plan.meta)
            compiled = _lower_compile(plan)
        t1 = time.perf_counter()
        rec["wall"] = {"production_compile_s": t1 - t0}
        rec["scanned_counters"] = hlo_counters(compiled)  # body-once (reference)
        mem = compiled.memory_analysis()
        per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        rec["memory"] = {k: float(getattr(mem, k)) for k in
                         ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes")}
        rec["per_device_bytes"] = float(per_dev)
        rec["fits_16gb"] = bool(per_dev < 16e9)
        # XLA-CPU has no native bf16 FMA: it materializes f32 copies of bf16
        # operands (hoisted out of loops for stacked weights/caches).  A TPU
        # lowering keeps those bf16.  Estimate the TPU-native footprint by
        # netting out the materialized f32 convert results (upper-bound
        # correction; both numbers are reported).
        import re as _re

        txt = compiled.as_text()
        bf16_shapes = set(_re.findall(r"bf16\[([0-9,]*)\]", txt))
        shadows = set()
        # allocating ops only (GTE/tuple/parameter are views of the same buffer)
        for m in _re.finditer(
                r"(%[\w\.\-]+) = f32\[([0-9,]*)\]\S* "
                r"(?:convert|copy|dynamic-update-slice|fusion|broadcast|select)\(", txt):
            if m.group(2) in bf16_shapes:
                shadows.add((m.group(1), m.group(2)))
        from ..core.telemetry import _shape_bytes

        f32_shadow = float(sum(_shape_bytes(f"f32[{dims}]") for _, dims in shadows
                               if _shape_bytes(f"f32[{dims}]") > 64e6))
        floor = float(mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes)
        rec["f32_shadow_bytes"] = f32_shadow
        rec["tpu_memory_estimate_bytes"] = max(floor, per_dev - f32_shadow)
        rec["fits_16gb_tpu_est"] = bool(rec["tpu_memory_estimate_bytes"] < 16e9)

        # ---- counter passes: unrolled @ k=1,2 depth units; extrapolate.
        if counters:
            K = depth_units(cfg)
            cs = []
            with _temp_settings(_counter_overrides(shape.seq_len)):
                for k in (1, 2):
                    p_k = build_cell(arch, shape_name, mesh, multi_pod=multi_pod,
                                     microbatches=microbatches, depth_k=k)
                    cs.append(hlo_counters(_lower_compile(p_k)))
            t2 = time.perf_counter()
            rec["wall"]["counter_passes_s"] = t2 - t1
            keys = set(cs[0]) | set(cs[1])
            extrap = {k: cs[0].get(k, 0.0) + (K - 1) * (cs[1].get(k, 0.0) - cs[0].get(k, 0.0))
                      for k in keys}
            rec["counter_passes"] = {"k1": cs[0], "k2": cs[1], "units": K}
            # Pallas flash attention keeps scores in VMEM: model its HBM
            # traffic instead of the jnp fallback's (see launch/adjust.py)
            if current_settings(contexts=False)["flash_attention"]["impl"] == "pallas" and not cfg.attn_free:
                from .adjust import attention_adjustment

                adj = attention_adjustment(cfg, shape, mesh, plan.rules)
                extrap["bytes_accessed"] = max(
                    0.0, extrap.get("bytes_accessed", 0.0) - adj["delta_bytes"])
                rec["pallas_adjustment"] = adj
            rec["counters"] = extrap
            c = extrap
            rec["roofline"] = {
                "compute_s": c.get("flops", 0.0) / HW["peak_flops_bf16"],
                "memory_s": c.get("bytes_accessed", 0.0) / HW["hbm_bw"],
                "collective_s": c.get("collective_bytes", 0.0) / HW["ici_bw"],
            }
            terms = rec["roofline"]
            rec["bottleneck"] = max(terms, key=terms.get)
            step_s = max(terms.values())
            rec["step_time_bound_s"] = step_s
            mf = plan.meta["model_flops"] / rec["chips"]   # per-chip useful flops
            rec["useful_flops_ratio"] = mf / max(c.get("flops", 1.0), 1.0)
            # roofline fraction: useful model flops over peak for the
            # bound-derived step time (the score we hillclimb)
            rec["roofline_fraction"] = (mf / HW["peak_flops_bf16"]) / max(step_s, 1e-12)
        rec["os_counters"] = os_counters()
    except Exception as e:  # a failure here is a sharding/memory bug
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=25)
    finally:
        undo()
    return rec


def cell_path(out_dir: Path, arch: str, shape: str, mesh: str) -> Path:
    return out_dir / f"{arch}__{shape}__{mesh}.json"


def main() -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run sweep")
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = per-arch default (4 for >40B train cells)")
    ap.add_argument("--set", action="append", default=[], metavar="comp.key=val",
                    help="MLOS tunable override (repeatable)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="", help="suffix for result files (perf experiments)")
    args = ap.parse_args()

    for s in args.set:
        apply_overrides(parse_override(s))

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else ALL_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if not (args.all or args.arch or args.shape):
        ap.error("pass --all or --arch/--shape")

    n_err = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                tag = f"{mesh_name}{('__' + args.tag) if args.tag else ''}"
                path = cell_path(out_dir, arch, shape, tag)
                if path.exists() and not args.force:
                    rec = json.loads(path.read_text())
                    print(f"[cached] {arch:24s} {shape:12s} {mesh_name:6s} {rec['status']}")
                    continue
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, multi, microbatches=args.microbatches)
                rec["tunable_overrides"] = args.set
                path.write_text(json.dumps(rec, indent=1))
                dt = time.perf_counter() - t0
                msg = rec["status"]
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    msg += (f" mem={rec['per_device_bytes']/1e9:.2f}GB"
                            f" compute={r['compute_s']*1e3:.2f}ms"
                            f" memory={r['memory_s']*1e3:.2f}ms"
                            f" coll={r['collective_s']*1e3:.2f}ms"
                            f" bound={rec['bottleneck'].split('_')[0]}")
                elif rec["status"] == "error":
                    n_err += 1
                    msg += " " + rec["error"][:120]
                print(f"[{dt:6.1f}s] {arch:24s} {shape:12s} {mesh_name:6s} {msg}", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
