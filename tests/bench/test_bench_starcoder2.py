"""The code-completion cell of StarCoder2-15B's stage on the CPU: the cell
rehearsed at a tiny size through the harness's own functions, ``correct``
false when the served token is altered, its limit between the program and
the int8 control at grouped, biased GELU widths, and the reader of the
prefill programs' share of the device's busy time."""
import json

import pytest
from benchcells import ROOT, cpu_run, tiny_cell
from benchkit import cell as C
from benchkit import readers, serve
from benchkit import trace as T

import control

WORKLOAD = "starcoder2-codecomp-steady"


def test_cell_rehearsed():
    cell = tiny_cell(WORKLOAD, "float32")
    assert cell.config["num_key_value_heads"] < cell.config["num_attention_heads"]
    assert cell.config["sliding_window"] and cell.traffic["regime"] == "below_capacity"
    out = cpu_run(cell)
    serve.run(cell, 2**31 + 15, 2.0, None, out)
    res = out.result(cell.end_to_end, dict(out.e2e, setup_s=out.setup_s))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0, res["checks"]
    assert set(res["metrics"]) == {"ttft_p95_s", "tpot_p95_ms", "setup_s"}
    values, _ = readers.per_layer(cell, out, {"bf16_flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9}, None)
    host = {m["name"] for m in cell.per_layer if m["source"] != "device_trace"}
    assert host and host <= set(values)
    json.dumps(res)


def test_altered_token_is_not_correct(monkeypatch):
    from repro.runtime import serve_loop

    init = serve_loop.BatchedServer.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def altered(p, tok, caches, pos, done):
            nxt, caches, pos, done = decode(p, tok, caches, pos, done)
            return (nxt + 1) % self.cfg.vocab_size, caches, pos, done
        self._decode = altered

    monkeypatch.setattr(serve_loop.BatchedServer, "__init__", broken_init)
    cell = tiny_cell(WORKLOAD, "float32")
    out = cpu_run(cell)
    serve.run(cell, 54321, 1.0, None, out)
    assert out.failed == 0 and not out.correct, out.checks


def test_limit_refuses_int8_and_holds_the_program():
    """16 query heads over 4 KV heads, biases, GELU, d 2048, 2 layers, in
    the configuration's bfloat16: the int8 control reads above the cell's
    limit and the program at least 3x below it."""
    cell = tiny_cell(WORKLOAD)
    cell.config.update(hidden_size=2048, intermediate_size=4096, num_hidden_layers=2,
                       num_attention_heads=16, num_key_value_heads=4, vocab_size=4096)
    cell.config["deployment"] = dict(cell.config["deployment"], capacity=256, max_batch=8)
    cell.traffic["output_len"] = dict(cell.traffic["output_len"], min=32, max=120, median=80)
    cell.traffic["check_tokens"] = 800
    row = control.readings(cell, 5, 4.0)
    limit = cell.limits["served_logit_gap_mean"]
    prog, ctrl = row["program"]["served_logit_gap_mean"], row["control"]["served_logit_gap_mean"]
    assert ctrl > limit and prog * 3 <= limit, (prog, ctrl, limit)


def _reduced(modules):
    return {"window_s": 1.0, "busy_s": 0.8,
            "modules": {k: {"count": n, "seconds": t} for k, (n, t) in modules.items()}}


def test_prefill_share_of_a_made_up_window():
    read = C.reader("prefill_share.tpot")
    red = _reduced({"jit__fused_step": (20, 0.3), "jit__lambda": (3, 0.4),
                    "jit__install": (3, 0.1), "jit_draw": (1, 0.05)})
    assert read({"trace": red}) == pytest.approx(100.0 * 0.5 / 0.8)
    assert read({"trace": _reduced({"jit__fused_step": (20, 0.8)})}) == 0.0
    assert read({"trace": None}) is None


def test_prefill_share_of_a_trace_recorded_on_the_chip():
    """One second of ``olmo1b-chat`` traced on a TPU v5e."""
    spans, devices = T.read_planes(str(ROOT / "tests" / "bench" / "data" / "chat_1s.xplane.pb"))
    value = C.reader("prefill_share.tpot")({"trace": T.reduce(spans, devices)})
    assert 0.0 <= value <= 100.0
