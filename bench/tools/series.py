"""Run one cell several times, one process per run, and keep each result.

    python3 bench/tools/series.py --workload <name> --seeds <n> ... [--seconds 40]
        [--trace 0|1] [--out DIR]

The parent never imports JAX, so each child gets the chip.  Each run's last
stdout line (the result) and the end of its stderr go, one JSON line per
run, to ``<out>/<workload>.t<trace>.jsonl`` (default ``bench/_cache/series``);
a summary of every metric follows at the end: median, quartiles and the
spread (IQR over median) as Python's ``statistics.quantiles`` gives them.
Not part of a benchmark run.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values):
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return {"median": statistics.median(values), "q1": q1, "q3": q3,
            "spread": (q3 - q1) / statistics.median(values) if statistics.median(values) else None}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=int, default=40)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out", default=os.path.join(ROOT, "bench", "_cache", "series"))
    p.add_argument("--timeout", type=int, default=1200)
    args = p.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.t{args.trace}.jsonl")
    rows = []
    for seed in args.seeds:
        t0 = time.time()
        cmd = [sys.executable, "bench/run.py", "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        try:
            r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=args.timeout)
            rc, out, err = r.returncode, r.stdout, r.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out.decode() if isinstance(out, bytes) else out
            err = err.decode() if isinstance(err, bytes) else err
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            result = json.loads(last)
        except json.JSONDecodeError:
            result = None
        row = {"seed": seed, "rc": rc, "wall_s": time.time() - t0, "result": result,
               "notes": [ln for ln in out.splitlines() if not ln.startswith("{")][-25:],
               "stderr_tail": err[-3000:]}
        rows.append(row)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        short = {k: v["value"] for k, v in (result or {}).get("metrics", {}).items()}
        print(f"SERIES {args.workload} seed={seed} rc={rc} wall={row['wall_s']:.1f}s "
              f"correct={(result or {}).get('correct')} {json.dumps(short)} "
              f"checks={json.dumps((result or {}).get('checks'))}", flush=True)
        if result is None:
            print(err[-2000:], flush=True)
    metrics = {}
    for row in rows:
        for k, v in ((row["result"] or {}).get("metrics") or {}).items():
            metrics.setdefault(k, []).append(v["value"])
    print("SUMMARY " + json.dumps({k: spread(v) for k, v in metrics.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
