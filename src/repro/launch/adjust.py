"""Pallas-kernel roofline adjustment.

The dry-run lowers pure-XLA reference attention (Mosaic kernels can't lower
on the CPU host platform), which materializes the (Sq × Skv) score tensors to
HBM.  The Pallas flash kernel keeps them in VMEM: its HBM traffic is just the
Q/K/V/O tiles (+ gradient counterparts when trained).  When the MLOS settings
select ``impl=pallas``, the dry-run replaces the *measured* per-layer jnp
attention bytes with the kernel's ideal traffic:

    delta_per_layer = bytes(jnp attention, measured by standalone lowering
                            at the cell's exact sharded geometry)
                    - bytes_ideal

    bytes_ideal     = T · Σ |Q|,|K|,|V|,|O|   (per-device local sizes)
      T = 1 traversal set for inference (read QKV, write O)
      T = 15/4 · fwd set for training: fwd(4) + remat-recompute(4) +
          bwd reads q,k,v,dO + writes dQ,dK,dV (7) ⇒ 15 tensor traversals.

FLOPs are NOT adjusted (the kernel does the same matmuls); collective terms
are NOT adjusted (the SP boundary gathers are real on TPU too).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core.telemetry import hlo_counters
from ..kernels.flash_attention import ops as attn_ops
from ..models.attention import attn_cache_spec, kv_head_major
from ..models.config import ModelConfig
from ..models.layers import P, dtype_of
from ..parallel import sharding as shd
from .shapes import Shape

__all__ = ["attention_adjustment", "attn_layers_per_unit"]


def attn_layers_per_unit(cfg: ModelConfig) -> int:
    """Self-attention calls per depth unit (cross-attn excluded: conservative)."""
    return {"dense": 1, "moe": 1, "hybrid": 1, "ssm": 0,
            "encdec": 2,                       # enc self + dec self per paired unit
            "vlm": 1}[cfg.family] * (cfg.cross_attn_period if cfg.family == "vlm" else 1)


def _local_bytes(struct: jax.ShapeDtypeStruct, mesh: Mesh) -> int:
    n = math.prod(struct.shape) * struct.dtype.itemsize
    spec = struct.sharding.spec
    sizes = dict(mesh.shape)
    denom = 1
    for ax in spec:
        if ax is None:
            continue
        for a in ((ax,) if isinstance(ax, str) else ax):
            denom *= sizes[a]
    return n // denom


def attention_adjustment(cfg: ModelConfig, shape: Shape, mesh: Mesh,
                         rules: shd.Rules) -> Dict[str, float]:
    """Per-DEVICE bytes delta for the whole model (all layers), ≥ 0."""
    if cfg.attn_free or attn_layers_per_unit(cfg) == 0:
        return {"delta_bytes": 0.0, "bytes_jnp": 0.0, "bytes_ideal": 0.0}
    dt = dtype_of(cfg)
    b = shape.global_batch
    if shape.kind == "decode":
        sq, skv = 1, cfg.cache_len(shape.seq_len)
    else:
        sq = skv = shape.seq_len

    def struct(p):
        return jax.ShapeDtypeStruct(p.shape, dt, sharding=shd.sharding_for(p, rules, mesh))

    q = struct(P((b, sq, cfg.n_heads, cfg.hd), ("batch", None, "heads", None)))
    if shape.kind == "decode":   # the KV cache as decode holds it
        k = struct(attn_cache_spec(cfg, b, shape.seq_len)["k"])
    else:
        k = struct(P((b, skv, cfg.n_kv_heads, cfg.hd), ("batch", None, "kv_heads", None)))
    v = k

    train = shape.kind == "train"

    def attn(q, k, v):
        impl = "unrolled" if shape.kind != "decode" else None
        if shape.kind == "decode":
            out = attn_ops.decode_attention(q, k, v, jnp.asarray(skv - 1, jnp.int32),
                                            kv_head_major=kv_head_major(cfg))
        else:
            out = attn_ops.flash_attention(q, k, v, causal=True, window=cfg.window,
                                           impl="unrolled")
        return out

    if train:
        fn = lambda q, k, v: jnp.sum(jnp.square(attn(q, k, v).astype(jnp.float32)))
        fn = jax.grad(fn, argnums=(0, 1, 2))
    else:
        fn = attn
    compiled = jax.jit(fn).lower(q, k, v).compile()
    c = hlo_counters(compiled)
    bytes_jnp = c.get("bytes_accessed", 0.0)

    per_tensor = (_local_bytes(q, mesh) + 2 * _local_bytes(k, mesh)
                  + _local_bytes(q, mesh))                       # Q + K + V + O
    traversals = 15.0 / 4.0 if train else 1.0
    bytes_ideal = per_tensor * traversals
    from .specs import depth_units  # late import (specs → shapes only; no cycle)

    n_layers = attn_layers_per_unit(cfg) * depth_units(cfg)
    delta = max(0.0, (bytes_jnp - bytes_ideal)) * n_layers
    return {"delta_bytes": float(delta), "bytes_jnp": float(bytes_jnp),
            "bytes_ideal": float(bytes_ideal), "attn_layers": int(n_layers)}
