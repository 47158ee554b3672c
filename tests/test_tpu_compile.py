"""Ahead-of-time compiles for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached, and compiles
for a described ``v5e:2x2`` topology.  That catches what interpret mode
cannot: block shapes off the (8, 128) tiling, VMEM refs Mosaic refuses, a
program that does not fit the chip's 16 GB.  Nothing runs, so these say
nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, and every test worker imports
every test file.  The persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back here).
"""
import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import pytest
from conftest import shaped_instructions
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import decode_attention_pallas, flash_attention_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd.kernel import ssd_pallas
from repro.models import model as M
from repro.runtime.serve_loop import BatchedServer

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure here means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)


def test_flash_attention_kernel_compiles(one_chip):
    q = _sds((1, 2048, 16, 128), jnp.bfloat16, one_chip)
    c = _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=512, block_kv=512), q, q, q)
    assert "tpu_custom_call" in c.as_text()


# the decode kernel at both cells' widths: (layers, slots, C, K, head-major)
DECODE = {
    "olmo-1b": (16, 32, 2048, 16, False),
    "starcoder2-15b-stage": (10, 32, 4096, 4, True),
}


@pytest.mark.parametrize("name", list(DECODE))
def test_decode_attention_kernel_compiles(one_chip, name):
    """The whole stacked cache goes in (no layer is sliced out for it), the
    kernel copies what it reads, and nothing else is allocated."""
    n_layers, b, c, n_kv, head_major = DECODE[name]
    h = 48 if head_major else n_kv
    layer = (b, n_kv, c, 128) if head_major else (b, c, n_kv, 128)
    kv = _sds((n_layers, *layer), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q, k, v, n, i: decode_attention_pallas(q, k, v, n, i, kv_head_major=head_major),
        _sds((b, 1, h, 128), jnp.bfloat16, one_chip), kv, kv,
        _sds((b,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# (K, H, D, head-major, kernel?): the kernel where ``decode_block`` finds a
# block, the reference where the TPU's tiling refuses the copy (D of 64:
# hymba head-major, seamless sequence-major)
DECODE_DISPATCH = [
    (16, 16, 128, False, True),
    (4, 12, 128, True, True),
    (5, 25, 64, True, False),
    (16, 16, 64, False, False),
]


@pytest.mark.parametrize("n_kv,h,d,head_major,kernel", DECODE_DISPATCH)
def test_decode_attention_op_lowers_for_the_tpu(one_chip, n_kv, h, d, head_major, kernel):
    """``ops.decode_attention`` on a stacked cache, lowered for the TPU,
    compiles either way and holds the kernel exactly where it can run."""
    from repro.kernels.flash_attention import ops

    layer = (4, n_kv, 512, d) if head_major else (4, 512, n_kv, d)
    kv = _sds((2, *layer), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q, k, v, p, i: ops.decode_attention(q, k, v, p, kv_head_major=head_major,
                                                   layer=i),
        _sds((4, 1, h, d), jnp.bfloat16, one_chip), kv, kv,
        _sds((4,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    assert ("tpu_custom_call" in compiled.as_text()) == kernel


@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_kernel_compiles(one_chip, residual):
    x = _sds((2048, 2048), jnp.bfloat16, one_chip)
    scale = _sds((2048,), jnp.bfloat16, one_chip)
    if residual:
        c = _compile(lambda x, r, s: rmsnorm_pallas(x, s, r), x, x, scale)
    else:
        c = _compile(lambda x, s: rmsnorm_pallas(x, s), x, scale)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_kernel_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-780m")
    b, s, h, p = 1, 2048, cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    assert (h, p, n) == (48, 64, 128)
    f32, bf16 = jnp.float32, jnp.bfloat16
    c = _compile(lambda x, dt, A, B, C: ssd_pallas(x, dt, A, B, C, chunk=128),
                 _sds((b, s, h, p), bf16, one_chip), _sds((b, s, h), f32, one_chip),
                 _sds((h,), f32, one_chip), _sds((b, s, g, n), bf16, one_chip),
                 _sds((b, s, g, n), bf16, one_chip))
    assert "tpu_custom_call" in c.as_text()


# the serve engine at a deployment's widths: repo config, depth (None as
# published), cache capacity, slots, and the prefill width compiled
SERVE = {
    "olmo-1b": ("olmo-1b", None, 2048, 8, 512),
    "starcoder2-15b-stage": ("starcoder2-15b", 10, 4096, 32, 2048),
}


class Compiled(NamedTuple):
    srv: BatchedServer
    params: Any            # shapes on one chip
    width: int             # the prefill width to compile
    decode: Any            # the donated fused decode step, compiled
    layer: tuple           # one layer's K (or V) shape in the stacked cache


@pytest.fixture(scope="module")
def serve_compiled(one_chip):
    """``get(name)``: a deployment's :class:`Compiled`, built once per
    module."""
    built = {}

    def get(name):
        if name not in built:
            repo_config, layers, capacity, max_batch, width = SERVE[name]
            cfg = get_config(repo_config)
            if layers:
                cfg = dataclasses.replace(cfg, n_layers=layers)
            params = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
            params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), params)
            srv = BatchedServer(None, cfg, capacity=capacity, mode="continuous",
                                settings={"max_batch": max_batch})
            caches = jax.eval_shape(lambda: M.init_cache(cfg, max_batch, capacity, srv._enc_len))
            caches = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), caches)
            i32 = _sds((max_batch,), jnp.int32, one_chip)
            done = _sds((max_batch,), jnp.bool_, one_chip)
            decode = srv._decode.lower(params, i32, caches, i32, done).compile()
            built[name] = Compiled(srv, params, width, decode, caches["k"].shape[1:])
        return built[name]

    return get


def _prefill_bytes(get, name, one_chip) -> float:
    d = get(name)
    toks = _sds((1, d.width), jnp.int32, one_chip)
    return _bytes(d.srv._prefill_fn.lower(d.params, toks, None).compile())


def _assert_kv_in_stack(get, name) -> None:
    """No buffer shaped like one layer's K or V, nor like that layer sliced
    from the stack with its leading 1: the token is written into the stacked
    cache in place and attention reads its layer where it lies (the copying
    form materialised four such buffers per layer)."""
    d = get(name)
    text = d.decode.as_text()
    for shape in (d.layer, (1, *d.layer)):
        assert shaped_instructions(text, shape, in_fusions=False) == [], shape
    layer_bytes = 2 * math.prod(d.layer)  # bf16
    assert d.decode.memory_analysis().temp_size_in_bytes < layer_bytes


def test_serve_prefill_compiles_and_fits(one_chip, serve_compiled):
    assert _prefill_bytes(serve_compiled, "olmo-1b", one_chip) < HBM_BYTES


def test_serve_fused_decode_compiles_and_fits(serve_compiled):
    assert _bytes(serve_compiled("olmo-1b").decode) < HBM_BYTES


def test_serve_fused_decode_keeps_kv_in_the_stack(serve_compiled):
    _assert_kv_in_stack(serve_compiled, "olmo-1b")


def test_starcoder2_stage_prefill_compiles_and_fits(one_chip, serve_compiled):
    """The widest prefill of the code-completion cell: 2048 tokens, grouped
    KV heads, biased GELU MLP."""
    assert _prefill_bytes(serve_compiled, "starcoder2-15b-stage", one_chip) < HBM_BYTES


def test_starcoder2_stage_fused_decode_compiles_and_fits(serve_compiled):
    """32 slots of the 4096-token ring beside one stage's weights."""
    assert _bytes(serve_compiled("starcoder2-15b-stage").decode) < HBM_BYTES


def test_starcoder2_stage_fused_decode_keeps_kv_in_the_stack(serve_compiled):
    """The in-place write and read hold where the cache is a ring (capacity
    equals the window, the slot is ``pos % C``) and 48 query heads share
    4 KV heads, whose caches are stored head-major."""
    d = serve_compiled("starcoder2-15b-stage")
    cfg = d.srv.cfg
    assert d.srv.capacity == cfg.window and cfg.n_heads // cfg.n_kv_heads == 12
    assert d.layer == (32, 4, 4096, 128)
    _assert_kv_in_stack(serve_compiled, "starcoder2-15b-stage")


@pytest.mark.parametrize("name", list(SERVE))
def test_serve_fused_decode_runs_the_decode_kernel(serve_compiled, name):
    """Lowered for the TPU, decode attention dispatches to the Pallas
    kernel: one ``decode_attention`` custom call in the layer loop, handed
    the whole stacked K and V."""
    d = serve_compiled(name)
    calls = [line for line in d.decode.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0].lstrip().startswith("%decode_attention")
    stack = "bf16[" + ",".join(map(str, (d.srv.cfg.n_layers, *d.layer))) + "]"
    assert calls[0].count(stack) == 2
