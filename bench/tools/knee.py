"""Find a serving cell's knee: the highest offered rate at which the queue
does not grow across the window.

    python3 bench/tools/knee.py --workload <name> --rates 4 6 8 ... [--seconds 20]

One process, one server: the cell's configuration and traffic shapes, warmed
once; then a window at each rate, drained before the next.  Prints one JSON
line per rate: the queue at the window's quarters, requests offered and
finished, tokens per second and the TTFT p95.  Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    bench_run.prepare_env()
    import numpy as np

    from benchkit import cell as C
    from benchkit import chip, reference, serve, traffic
    from repro.runtime.serve_loop import BatchedServer

    cell = C.load(args.workload)
    chip.require(cell.chips)
    a = reference.Arch.from_file(cell.config)
    dep, mix = cell.config["deployment"], cell.traffic
    params = reference.make_params(a, args.seed, cell.config["dtype"])
    srv = BatchedServer(params, C.program_config(cell.config), capacity=dep["capacity"],
                        eos_id=-1, mode="continuous", settings={"max_batch": dep["max_batch"]})
    caps = dep["capacity"]
    widths = set()
    plans = {}
    for rate in args.rates:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        plans[rate] = traffic.requests(m, args.seed, args.seconds, a.vocab)
        widths |= {serve.width_of(len(r.prompt), caps) for r in plans[rate]}
    serve.warm(srv, sorted(widths), a.vocab)
    for rate in args.rates:
        w = serve.drive(srv, plans[rate], args.seconds, annotate=False)
        span = w.t1 - w.t0
        q = [next((s["queue"] for s in w.steps if s["end"] >= w.t0 + f * span), None)
             for f in (0.25, 0.5, 0.75)] + [w.steps[-1]["queue"] if w.steps else None]
        row = {"rate": rate, "offered": len(plans[rate]), "queue_at_quarters": q,
               "finished_in_window": sum(s["finished"] for s in w.steps),
               "tokens_per_s": sum(s["tokens"] for s in w.steps) / span,
               "mean_live": float(np.mean([s["live"] for s in w.steps])) if w.steps else 0.0,
               "step_ms": 1e3 * float(np.median([s["end"] - s["start"] for s in w.steps]))}
        serve.drain(srv, w, 120.0)
        ttft = [w.first[r] - w.sched[r] for r in w.sched if r in w.first]
        row["ttft_p95_s"] = float(np.percentile(ttft, 95)) if ttft else None
        print("KNEE " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
