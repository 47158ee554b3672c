"""Readings that set a cell's limits: the program's sound runs and the control.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] [--seconds <s>]

For each seed, in one process: the program's numbers as a run of the cell
reads them, and the control's, the plain reference computed one precision
below the bfloat16 the configuration states.  A serving cell runs a short
window at the cell's own load and reads the control's first choice at every
position of the same sampled requests; its control computes every weight
product in int8 (the v5e's MXU path: activations scaled per token, weights
per output channel).  A training cell reads its set-up steps with float8
operands in the forward and backward, and besides the control the planted
fault "half of the batch left out, the mean taken over the rest", put in the
reference's place.  One JSON line per seed; not part of a benchmark run.
"""
from __future__ import annotations

import json
import sys
import time

import run as bench_run



def readings(cell, seed: int, seconds: float) -> dict:
    """One seed's readings: the program's numbers and the control's (and,
    for training, the half-batch fault's)."""
    from benchkit import reference, serve, train
    from benchkit.report import Run

    out = Run(time.perf_counter(), {k: float("inf") for k in cell.limits})
    row = {"workload": cell.name, "seed": seed}
    if cell.kind == "serve":
        serve.run(cell, seed, seconds, None, out)
        rec = out.records
        ctrl, n_req, n_tok = serve.served_check(
            rec["params"], rec["arch"], rec["finished"], rec["window"], seed, cell.traffic,
            cell.config["deployment"], quant="int8")
        row.update(program=out.checks, control=ctrl, sample={"requests": n_req, "tokens": n_tok})
    else:
        train.run(cell, seed, 0.0, None, out)
        rec = out.records
        a, hyper = rec["arch"], cell.traffic["hyper"]
        p0 = lambda: reference.make_params(a, seed, cell.config["dtype"])  # noqa: E731
        q = reference.train_readings(p0, rec["batches"], a, hyper, quant="fp8")
        half = [(t[: len(t) // 2], lab[: len(lab) // 2]) for t, lab in rec["batches"]]
        h = reference.train_readings(p0, half, a, hyper)
        row.update(program=out.checks, control=train.gaps(q, rec["ref"]),
                   half_batch=train.gaps(h, rec["ref"]))
    row.update(e2e=out.e2e, attempted=out.attempted, failed=out.failed)
    return row


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    bench_run.prepare_env()
    from benchkit import cell as C
    from benchkit import chip

    cell = C.load(args.workload)
    chip.require(cell.chips)
    from repro.core.compilecache import clear_jit_registry

    for seed in args.seeds:
        print("CONTROL " + json.dumps(readings(cell, seed, args.seconds)), flush=True)
        # the registry's compiled closures hold the last server, and with it
        # its weights: drop them before the next seed draws its own
        clear_jit_registry()
    return 0


if __name__ == "__main__":
    sys.exit(main())
