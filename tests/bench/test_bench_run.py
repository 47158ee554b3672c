"""The harness rehearsed on the CPU at a tiny size: a serve cell and the
train cell through the harness's own functions, the result line's keys, the
refusal off the TPU, and ``correct`` coming out false when the timed path
underneath is broken.  The tiny configurations run in float32, where a
sound run meets the reference to rounding, so each fault shows alone."""
import json
import os
import subprocess
import sys

import pytest
from benchcells import ROOT, cpu_run, tiny_cell
from benchkit import readers, serve, train

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _result(cell, out, trace):
    if trace:
        values, _ = readers.per_layer(cell, out, {"bf16_flops_per_s": 197e12,
                                                  "hbm_bytes_per_s": 819e9}, None)
        return out.result(cell.per_layer, values, {"device_ops": [], "idle_gaps": []})
    return out.result(cell.end_to_end, dict(out.e2e, setup_s=out.setup_s))


@pytest.mark.parametrize("workload", ["olmo1b-chat", "starcoder2-codecomp"])
def test_serve_cell_rehearsed(workload):
    cell = tiny_cell(workload, "float32")
    out = cpu_run(cell)
    serve.run(cell, 2**31 + 11, 2.0, None, out)
    res = _result(cell, out, trace=False)
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == names and "setup_s" in names
    traced = _result(cell, out, trace=True)
    host_metrics = {m["name"] for m in cell.per_layer if m["source"] != "device_trace"}
    assert host_metrics <= set(traced["metrics"])
    assert all(m["unit"] for m in traced["metrics"].values())
    json.dumps(res)


def test_train_cell_rehearsed():
    cell = tiny_cell("olmo1b-train", "float32")
    out = cpu_run(cell)
    train.run(cell, 77, 1.0, None, out)
    res = _result(cell, out, trace=False)
    assert RESULT_KEYS <= set(res) and res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert "mfu.train" in _result(cell, out, trace=True)["metrics"]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", "olmo1b-chat", "--seed",
                        "5", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and '"correct"' not in r.stdout


# ---------------------------------------------------------------- faults
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
    cell = tiny_cell("olmo1b-chat", "float32")
    out = cpu_run(cell)
    serve.run(cell, 12345, 1.0, None, out)
    assert out.failed == 0 and not out.correct, out.checks


def _broken_step(monkeypatch, wrap):
    from repro.runtime import steps

    real = steps.jit_train_step
    monkeypatch.setattr(steps, "jit_train_step", lambda *a, **k: wrap(real(*a, **k)))


def test_state_left_unchanged_is_not_correct(monkeypatch):
    def wrap(step):
        def same(state, batch, lr_scale):
            _, m = step(state, batch, lr_scale)
            return state, m
        return same

    _broken_step(monkeypatch, wrap)
    cell = tiny_cell("olmo1b-train", "float32")
    out = cpu_run(cell)
    train.run(cell, 78, 0.0, None, out)
    assert not out.correct, out.checks


def test_half_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def half(state, batch, lr_scale):
            b = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:b] for k, v in batch.items()}, lr_scale)
        return half

    _broken_step(monkeypatch, wrap)
    cell = tiny_cell("olmo1b-train", "float32")
    out = cpu_run(cell)
    train.run(cell, 79, 0.0, None, out)
    assert not out.correct, out.checks
