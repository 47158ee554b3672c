"""The dry run's attention adjustment compiles decode attention against the
KV cache as the program holds it."""
import dataclasses

import jax
import pytest

from repro.configs import get_config
from repro.launch.adjust import attention_adjustment
from repro.launch.shapes import Shape
from repro.parallel import sharding as shd


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-15b"])
def test_decode_adjustment_reads_the_cache_as_decode_holds_it(name):
    """Sequence-major (olmo-1b, one query per KV head) and head-major
    (StarCoder2, 12 per KV head) caches: the ideal bytes are Q, K, V and O
    once each."""
    cfg = dataclasses.replace(get_config(name), n_layers=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    adj = attention_adjustment(cfg, Shape("d", "decode", 512, 4), mesh, shd.serve_rules())
    q = 4 * cfg.n_heads * cfg.hd * 2
    kv = 4 * cfg.cache_len(512) * cfg.n_kv_heads * cfg.hd * 2
    assert adj["bytes_ideal"] == 2 * q + 2 * kv
    assert adj["bytes_jnp"] > 0 and adj["attn_layers"] == 2
