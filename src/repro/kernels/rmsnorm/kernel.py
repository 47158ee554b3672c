"""Pallas TPU fused RMSNorm (+ residual add) kernel.

Row-blocked: each grid step normalizes ``block_rows`` rows of the flattened
(rows, d) input entirely in VMEM (one HBM read, one write — the fusion saves
the extra residual-add round-trip that XLA sometimes fails to fuse across
remat boundaries).  d should be a multiple of 128 (lane width).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm_pallas"]


def _kernel(x_ref, s_ref, o_ref, *, eps: float, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(out_dtype)


def _kernel_res(x_ref, r_ref, s_ref, o_ref, *, eps: float, out_dtype):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(out_dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, residual: Optional[jax.Array] = None,
                   eps: float = 1e-5, block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    """x: (..., d); scale: (d,). Returns rmsnorm(x [+ residual]) * scale."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)

    grid = (rows // block_rows,)
    x_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    s_spec = pl.BlockSpec((d,), lambda i: (0,))
    if residual is None:
        kern = functools.partial(_kernel, eps=eps, out_dtype=x.dtype)
        out = pl.pallas_call(
            kern, grid=grid, in_specs=[x_spec, s_spec], out_specs=x_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype), interpret=interpret,
        )(x2, scale)
    else:
        kern = functools.partial(_kernel_res, eps=eps, out_dtype=x.dtype)
        out = pl.pallas_call(
            kern, grid=grid, in_specs=[x_spec, x_spec, s_spec], out_specs=x_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype), interpret=interpret,
        )(x2, residual.reshape(rows, d), scale)
    return out.reshape(orig_shape)
