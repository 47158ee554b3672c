"""Pallas TPU flash-attention kernel (GQA + causal + sliding window).

TPU-native adaptation (not a CUDA port): the grid's last dimension iterates
KV blocks *sequentially* per core, so the online-softmax state (acc, m, l)
lives in VMEM scratch that persists across KV steps — no atomics, no
shared-memory reductions.  Q/K/V tiles are explicit BlockSpecs into VMEM;
matmul dims should be multiples of 128 to land on the MXU.  The running
max and sum are (block_q, 1) columns: 2-D, as Mosaic wants every VMEM ref.

block_q × block_kv are the MLOS auto-parameters (ops.py registers them);
fully-masked KV blocks are skipped with ``pl.when`` (causal / window).

Validated against ref.naive_attention in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, q_offset: int,
            block_q: int, block_kv: int, out_dtype):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = q_offset + qi * block_q
    k_lo = ki * block_kv

    # Skip KV blocks that are fully masked for this Q block.
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_lo + block_q - 1)
    if window:
        live = jnp.logical_and(live, k_lo + block_kv - 1 > q_lo - window)

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)                          # (bq, d)
        k = k_ref[...].astype(jnp.float32)                          # (bkv, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                         # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(out_dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, q_offset: int = 0,
    block_q: int = 512, block_kv: int = 512,
    scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, K, D) with H % K == 0. Returns (B, Sq, H, D).

    Heads are folded into the last dim (a free reshape), so each block is a
    (block, D) tile of a (S, H·D) slab: tile-aligned on the TPU when D is a
    multiple of 128, where a (block, 1, D) block of (S, H, D) is not.
    ``interpret=True`` runs the Pallas interpreter (tests off the TPU)."""
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    g = h // n_kv
    scale = scale or 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    if sq % block_q or sk % block_kv:
        raise ValueError(f"seq ({sq},{sk}) must divide blocks ({block_q},{block_kv})")

    grid = (b, h, sq // block_q, sk // block_kv)
    batch = pl.Squeezed()
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_kv=block_kv, out_dtype=q.dtype)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch, block_q, d), lambda bi, hi, qi, ki: (bi, qi, hi)),
            pl.BlockSpec((batch, block_kv, d), lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g)),
            pl.BlockSpec((batch, block_kv, d), lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g)),
        ],
        out_specs=pl.BlockSpec((batch, block_q, d), lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, sq, h * d), k.reshape(b, sk, n_kv * d), v.reshape(b, sk, n_kv * d))
    return out.reshape(b, sq, h, d)
