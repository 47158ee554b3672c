"""Helpers for the chip benchmark's CPU tests: the harness on the path, and
cells of the real ``BENCHMARK.json`` cut to a size the CPU runs in seconds.
(A module of its own name, not a ``conftest``: the suite's other tests
import ``tests/conftest.py`` by that name.)"""
import sys
import time
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def tiny_cell(workload: str, dtype: str = "bfloat16"):
    """The cell with its widths, depth, vocabulary and traffic cut down."""
    from benchkit import cell as C

    cell = off_benchmark_cell(workload) if workload in OFF_BENCHMARK else C.load(workload)
    c = dict(cell.config, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, vocab_size=256, dtype=dtype,
             num_key_value_heads=2 if cell.config["num_key_value_heads"] < cell.config[
                 "num_attention_heads"] else 4)
    if c.get("sliding_window"):
        c["sliding_window"] = 64
    cell.config = c
    t = dict(cell.traffic)
    if t["kind"] == "serve":
        c["deployment"] = dict(c["deployment"], capacity=128, max_batch=4)
        for key, lo, hi in (("prompt_len", 4, 60), ("output_len", 2, 24)):
            d = dict(t[key], min=lo, max=hi)
            if d["dist"] == "lognormal":
                d["median"] = (lo + hi) // 3
            t[key] = d
        t.update(check_tokens=40, drain_s=30)
    else:
        t.update(batch=2, seq_len=64,
                 doc_len=dict(t["doc_len"], x_min=4, median=8, min=2, max=100))
    cell.traffic = t
    return cell


# cells whose files the harness runs but whose entries wait in PERF.md's
# open questions: configuration, traffic, end-to-end metric, per-layer metrics
OFF_BENCHMARK = {
    "olmo1b-train": ("olmo-1b-trainstage", "pretrain-packed", "train_tokens_per_s",
                     {"mfu.train": "host_clock", "idle_share.train": "device_trace"}),
    "starcoder2-codecomp": ("starcoder2-15b-stage", "codecomp", "serve_tokens_per_s",
                            {"slot_occupancy.serve": "program_counter", "mfu.serve": "host_clock",
                             "prefill_roofline.serve": "device_trace",
                             "idle_share.serve": "device_trace"}),
}


def off_benchmark_cell(workload: str):
    """A cell from its files alone (configuration, traffic, limits)."""
    from benchkit import cell as C

    config, traffic, e2e, per_layer = OFF_BENCHMARK[workload]
    spec = C.load_json(C.ROOT / "BENCHMARK.json")
    return C.Cell(workload, 1,
                  C.load_json(C.BENCH / "configs" / f"{config}.json"),
                  C.load_json(C.BENCH / "traffic" / f"{traffic}.json"),
                  C.load_json(C.BENCH / "limits" / f"{workload}.json")["limits"],
                  [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
                  + [{"name": e2e, "unit": "tokens/s"}],
                  [{"name": n, "unit": "%", "source": src} for n, src in per_layer.items()])


def cpu_run(cell):
    from benchkit.report import Run

    out = Run(time.perf_counter(), cell.limits)
    out.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return out
