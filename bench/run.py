"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``'s ``workloads``)
names its configuration and its traffic; the files they name are read from
``bench/``.  Set-up (JAX start, weights from the seed, compiles and warm-up)
runs first, then the window of ``--seconds``, then the check against the
plain reference.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` traces the window and prints its per-layer metrics.  The last
line of standard output is the result; the numbers compared with their
limits are the last lines of standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / "_cache"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="DIR", default=None,
                   help="with --trace 1, write the trace under DIR and keep it")
    return p.parse_args(argv)


def prepare_env() -> None:
    """The program and this directory on the path, and JAX's compilation
    cache at a fixed path inside the checkout (the program takes the one
    ``JAX_COMPILATION_CACHE_DIR`` names)."""
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    # no eviction: an evicting cache keeps an access-time file per entry, and
    # writing those failed on the chip's machines, leaving every run cold
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"the program is not in this checkout: no {ROOT / 'src' / 'repro'}")
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    args = parse(argv)
    prepare_env()
    from benchkit import cell as C
    from benchkit import chip
    from benchkit.report import Run

    cell = C.load(args.workload)
    device, peaks = chip.require(cell.chips)
    out = Run(T_START, cell.limits)
    out.device = device
    out.note(f"device: platform={device['platform']} kind={device['kind']} "
             f"count={device['count']}")
    trace_dir = None
    if args.trace:
        trace_dir = args.keep_trace or str(CACHE / "trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if cell.kind == "serve":
        from benchkit import serve as kind
    elif cell.kind == "train":
        from benchkit import train as kind
    else:
        raise SystemExit(f"unknown traffic kind {cell.kind!r}")
    kind.run(cell, args.seed, args.seconds, trace_dir, out)

    breakdown = None
    if args.trace:
        from benchkit import readers

        values, red = readers.per_layer(cell, out, peaks, trace_dir)
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None:
            raise SystemExit("the trace holds no device work inside the window")
        out.device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        metrics = cell.per_layer
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        metrics = cell.end_to_end
    out.emit(out.result(metrics, values, breakdown))
    return 0


if __name__ == "__main__":
    sys.exit(main())
