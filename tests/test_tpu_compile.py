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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_pallas
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


@pytest.fixture(scope="module")
def olmo_server(one_chip):
    """The serve engine at olmo-1b widths, its params as shapes on one chip."""
    cfg = get_config("olmo-1b")
    params = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), params)
    srv = BatchedServer(None, cfg, capacity=2048, mode="continuous",
                        settings={"max_batch": 8})
    return srv, params


def test_serve_prefill_compiles_and_fits(one_chip, olmo_server):
    srv, params = olmo_server
    toks = _sds((1, 512), jnp.int32, one_chip)
    c = srv._prefill_fn.lower(params, toks, None).compile()
    assert _bytes(c) < HBM_BYTES


def test_serve_fused_decode_compiles_and_fits(one_chip, olmo_server):
    srv, params = olmo_server
    cfg, mb = srv.cfg, srv.max_batch
    caches = jax.eval_shape(lambda: M.init_cache(cfg, mb, srv.capacity, srv._enc_len))
    caches = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), caches)
    i32 = _sds((mb,), jnp.int32, one_chip)
    done = _sds((mb,), jnp.bool_, one_chip)
    c = srv._decode.lower(params, i32, caches, i32, done).compile()
    assert _bytes(c) < HBM_BYTES
