"""Peaks and the counts of work kept with the benchmark."""
import dataclasses

import pytest
import benchcells  # noqa: F401  (puts bench/ and src/ on the path)
from benchkit import chip, counts
from benchkit.reference import Arch

from repro.configs import get_config


def test_unknown_device_kind_is_an_error():
    assert chip.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="no peaks"):
        chip.peaks_for("TPU v9 imaginary")


def _arch(cfg):
    return Arch(d=cfg.d_model, f=cfg.d_ff, layers=cfg.n_layers, heads=cfg.n_heads,
                kv_heads=cfg.n_kv_heads, vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
                norm=cfg.norm, mlp="swiglu" if cfg.mlp == "swiglu" else "gelu_tanh",
                bias=cfg.use_bias, tied=cfg.tie_embeddings, window=cfg.window, eps=1e-5)


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-15b"])
def test_counts_agree_with_the_programs_parameter_count(name):
    cfg = dataclasses.replace(get_config(name).reduced(), head_dim=0)
    a = _arch(cfg)
    n_embed = cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    d, L = cfg.d_model, cfg.n_layers
    small = 0
    if cfg.use_bias:   # biases and LayerNorm scales/biases take part in no product
        small = L * (cfg.n_heads * a.hd + 2 * cfg.n_kv_heads * a.hd + d + cfg.d_ff + d + 4 * d) + 2 * d
    assert counts.layer_matmul_params(a) == cfg.param_count() - n_embed - small
    assert counts.weight_bytes(a) == 2 * (counts.layer_matmul_params(a) + d * cfg.vocab_size)


def test_pads_and_dead_cache_slots_are_not_counted():
    a = _arch(get_config("olmo-1b"))
    # a prompt of 300 real tokens costs the same whatever width it was padded to
    assert counts.prefill_flops(a, 300) < counts.prefill_flops(a, 512)
    per_tok = 2 * (counts.layer_matmul_params(a) + counts.head_params(a))
    # decode attends to the live context only, not the 2048-slot cache
    assert counts.decode_flops(a, 100) == per_tok + 4 * a.layers * a.heads * a.hd * 100
    assert counts.decode_flops(a, 100) < counts.decode_flops(a, 2048)


def test_window_caps_attention_work():
    a = _arch(get_config("starcoder2-15b"))
    assert counts.attn_flops(a, 10_000) == counts.attn_flops(a, a.window)
    full = counts.causal_attn_flops(dataclasses.replace(a, window=0), 6000)
    assert counts.causal_attn_flops(a, 6000) < full
    assert counts.causal_attn_flops(a, 4096) == counts.causal_attn_flops(
        dataclasses.replace(a, window=0), 4096)


def test_train_step_is_three_forwards():
    a = _arch(get_config("olmo-1b"))
    fwd_tok = 2 * (counts.layer_matmul_params(a) + counts.head_params(a))
    flops = counts.train_step_flops(a, 8, 2048)
    assert flops == pytest.approx(3 * 8 * (fwd_tok * 2048 + counts.causal_attn_flops(a, 2048)))
    assert 6 * 8 * 2048 * counts.layer_matmul_params(a) < flops


def test_roofline_time_takes_the_larger_bound():
    assert counts.roofline_time(197e12, 1.0, 197e12, 819e9) == pytest.approx(1.0)
    assert counts.roofline_time(1.0, 819e9, 197e12, 819e9) == pytest.approx(1.0)
