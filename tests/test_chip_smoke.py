"""chip_smoke.py rehearsed on the CPU: its serve, train and kernel phases at
``reduced()`` widths (the kernel interpreted), and its device check, which
must refuse a CPU platform and name it."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_config("olmo-1b").reduced()


def test_serve_phase_at_reduced_size(smoke, cfg, capsys):
    smoke.serve_phase(cfg, capacity=64, max_batch=4, max_new_tokens=8, n_requests=6,
                      prompt_lens=(5, 30))
    out = capsys.readouterr().out
    assert out.count("serve: flash_attention@") >= 2  # several width classes
    assert "6 requests" in out and "KV cache" in out


def test_train_phase_at_reduced_size(smoke, cfg, capsys):
    smoke.train_phase(cfg, n_layers=1, global_batch=2, seq_len=32, n_steps=2)
    out = capsys.readouterr().out
    assert "depth cut 2 -> 1 layers" in out and "2 steps" in out


def test_kernel_phase_interpreted(smoke, capsys):
    smoke.kernel_phase((1, 256, 2, 128), block=128, interpret=True)
    assert "max |err|" in capsys.readouterr().out


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="'cpu'"):
        smoke.device_check()


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
