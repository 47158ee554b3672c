"""A training cell: the program's compiled train step over packed batches.

Set-up draws the weights from the seed, builds the one object the window
drives (the compiled step, ``jit_train_step``, with its state) and takes it
through its first steps on the first batches, through the same call the
window makes.  From those it keeps what the check compares: each step's
loss, each leaf's first gradient as the optimizer got it (Adam's first
moment after one step, over ``1 - b1``) and each leaf's change after the
first steps.  The window then runs the same object on, one batch after
another, syncing on each step's loss as ``run_training`` does.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import reference, traffic
from .cell import Cell, program_config
from .report import Run
from .serve import check_layout

B1 = 0.9          # the program's AdamW first-moment decay
CHECK_STEPS = 3   # steps the reference follows


@jax.jit
def _grad_norms(m):
    return jax.tree.map(lambda t: jnp.sqrt(jnp.sum(jnp.square(t / (1 - B1)))), m)


@jax.jit
def _change_norms(p, p0):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0)


def _flat(tree) -> Dict[str, float]:
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers compared, each the worst of its kind.

    Loss: the largest relative gap over the steps.  Gradient and change: the
    worst leaf's gap between the program's norm and the reference's, over
    that leaf's reference norm or the median leaf's, whichever is larger.
    The change leaves out leaves whose reference gradient is under a
    thousandth of the median leaf's (they move by round-off alone).
    """
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))

    def worst(a: Dict[str, float], b: Dict[str, float], keep) -> float:
        med = float(np.median(list(b.values())))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in b if keep(k))

    gmed = float(np.median(list(ref["grad"].values())))
    moved = lambda k: ref["grad"][k] >= 1e-3 * gmed
    return {"loss_gap": loss,
            "grad_gap": worst(prog["grad"], ref["grad"], lambda k: True),
            "change_gap": worst(prog["change"], ref["change"], moved)}


def run(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str], out: Run) -> None:
    from repro.optim.adamw import adamw_init
    from repro.runtime.steps import TrainHyper, jit_train_step

    a = reference.Arch.from_file(cell.config)
    cfg = program_config(cell.config)
    job = cell.traffic
    hyper = job["hyper"]
    step = jit_train_step(cfg, TrainHyper(base_lr=hyper["base_lr"], warmup=hyper["warmup"],
                                          total=hyper["total"], weight_decay=hyper["weight_decay"],
                                          clip_norm=hyper["clip_norm"]))
    p0 = reference.make_params(a, seed, cell.config["dtype"])
    check_layout(p0, cfg)
    state = {"params": p0, "opt": adamw_init(p0), "step": jnp.zeros((), jnp.int32)}
    del p0   # the first step's state holds it; later it is drawn again from the seed
    batches = [traffic.packed_batch(job, seed, i, a.vocab) for i in range(CHECK_STEPS)]
    prog: Dict[str, Any] = {"loss": []}
    for i, (toks, labels) in enumerate(batches):
        state, m = step(state, {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}, 1.0)
        prog["loss"].append(float(m["loss"]))
        if i == 0:
            prog["grad"] = _flat(_grad_norms(state["opt"]["m"]))
    prog["change"] = _flat(_change_norms(state["params"], reference.make_params(a, seed, cell.config["dtype"])))
    out.note(f"train: {CHECK_STEPS} set-up steps, losses {prog['loss']}")
    out.setup_done()

    tokens = job["batch"] * job["seq_len"]
    losses: List[float] = []
    from jax.profiler import TraceAnnotation

    from .trace import WINDOW, capture

    def window() -> float:
        nonlocal state
        t0 = time.perf_counter()
        end = t0 + seconds
        i = CHECK_STEPS
        with TraceAnnotation(WINDOW):
            while time.perf_counter() < end:
                with TraceAnnotation("bench.data"):
                    toks, labels = traffic.packed_batch(job, seed, i, a.vocab)
                    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
                with TraceAnnotation("bench.train_step"):
                    state, m = step(state, batch, 1.0)
                    losses.append(float(m["loss"]))
                i += 1
        return time.perf_counter() - t0

    if trace_dir:
        with capture(trace_dir):
            window_s = window()
    else:
        window_s = window()
    out.read_memory()
    out.attempted = len(losses)
    out.failed = sum(not np.isfinite(x) for x in losses)
    out.note(f"train: {len(losses)} steps of {job['batch']} x {job['seq_len']} in "
             f"{window_s:.3f} s")
    out.end_to_end({"train_tokens_per_s": len(losses) * tokens / window_s})
    out.records = {"arch": a, "batch": job["batch"], "seq": job["seq_len"],
                   "steps_in_window": len(losses), "window_s": window_s}

    state = None
    gc.collect()
    ref = reference.train_readings(lambda: reference.make_params(a, seed, cell.config["dtype"]),
                                   batches, a, hyper)
    out.records.update(prog=prog, ref=ref, batches=batches)
    for k, v in gaps(prog, ref).items():
        out.check(k, v)
