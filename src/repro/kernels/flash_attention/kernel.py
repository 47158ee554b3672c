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

:func:`decode_attention_pallas` is the one-token decode against the stacked
KV cache: it reads, for each batch row, only the blocks that hold the row's
live context, and nothing for an empty row (validated against
ref.decode_attention, same file).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "decode_attention_pallas", "decode_block"]

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


# ------------------------------------------------------------------ decode
# A KV block holds at most this many cache elements (1 MiB of bfloat16):
# large enough that a block's DMA outlasts the loop's per-block work, small
# enough that K and V, double-buffered, stay far inside the scoped VMEM.
_DECODE_BLOCK_ELEMS = 1 << 19


def decode_block(c: int, n_kv: int, hd: int, head_major: bool) -> int:
    """Tokens per KV block of :func:`decode_attention_pallas` for a cache of
    capacity ``c`` with ``n_kv`` heads of ``hd``: the largest power of two
    that divides ``c`` and keeps a block within ``_DECODE_BLOCK_ELEMS``.

    0 where the cache's (8, 128) tiling in HBM cannot be copied by such
    blocks (the TPU compiler refuses the slice): ``hd`` not a multiple of
    128, or a second-minor extent not a multiple of 8 — the block's tokens
    head-major, the KV heads sequence-major.  The kernel does not run
    there."""
    cap = max(1, _DECODE_BLOCK_ELEMS // (n_kv * hd))
    blk = 1
    while blk * 2 <= cap and c % (blk * 2) == 0:
        blk *= 2
    if hd % 128 or (blk if head_major else n_kv) % 8:
        return 0
    return blk


def _decode_kernel(len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, nxt_ref, m_ref, l_ref, acc_ref, *,
                   blk: int, head_major: bool, group: int, scale: float):
    """One layer of decode attention.  A single loop runs over the live
    (row, block) pairs in row order, double-buffered: while block ``i`` is
    computed, the DMA of block ``i + 1`` (the next block of the row, or the
    first of the next live row) is in flight.

    A block of K (or V) is flattened to ``R = blk * K`` rows of ``hd``, one
    per (token, KV head), in the cache's own order.  Every query head takes
    its scores against all ``R`` rows in one MXU product and masks the rows
    of other KV heads, so both cache orders and any grouping share one
    body; the masked scores become exact zeros of ``p``."""
    rows = len_ref.shape[0]
    n_kv = kbuf.shape[1] if head_major else kbuf.shape[2]
    h, hd = q_ref.shape[1], q_ref.shape[2]
    r = blk * n_kv
    layer = layer_ref[0]

    def nblocks(b):
        return (len_ref[b] + blk - 1) // blk

    def back(i, carry):
        # nxt_ref[b]: the next row after b that reads anything (rows if none)
        nxt, total = carry
        b = rows - 1 - i
        nxt_ref[b] = nxt
        return jnp.where(len_ref[b] > 0, b, nxt), total + nblocks(b)

    first, total = jax.lax.fori_loop(0, rows, back, (jnp.int32(rows), jnp.int32(0)))
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(slot, b, j):
        at = ((layer, b, slice(None), pl.ds(j * blk, blk)) if head_major
              else (layer, b, pl.ds(j * blk, blk)))
        return (pltpu.make_async_copy(k_hbm.at[at], kbuf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[slot], sems.at[1, slot]))

    @pl.when(total > 0)
    def _prefetch_first():
        for c in copies(0, first, 0):
            c.start()

    # which KV head and which token of the block each of the R rows holds
    col = jax.lax.broadcasted_iota(jnp.int32, (h, r), 1)
    own = jax.lax.broadcasted_iota(jnp.int32, (h, r), 0) // group
    if head_major:
        same_head, tok = col // blk == own, col % blk
    else:
        same_head, tok = col % n_kv == own, col // n_kv

    def body(i, carry):
        b, j = carry
        slot = i % 2
        last = j + 1 == nblocks(b)
        nb = jnp.where(last, nxt_ref[b], b)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(i + 1 < total)
        def _prefetch_next():
            for c in copies(1 - slot, nb, nj):
                c.start()

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        kc, vc = copies(slot, b, j)
        kc.wait()
        k = kbuf[slot].reshape(r, hd)
        s = jax.lax.dot_general(q_ref[b], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = jnp.logical_and(same_head, tok < len_ref[b] - j * blk)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        vc.wait()
        v = vbuf[slot].reshape(r, hd)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _finalize():
            o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return nb, nj

    jax.lax.fori_loop(0, total, body, (first, jnp.int32(0)))


def decode_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array, layer: jax.Array, *,
    kv_head_major: bool = False, scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """Single-token attention of layer ``layer`` of a stacked KV cache.

    q: (B, 1, H, D); k/v: the whole stack, (L, B, C, K, D), or (L, B, K, C, D)
    with ``kv_head_major``; H % K == 0.  ``lengths`` (B,) int32: the cache
    slots ``[0, lengths[b])`` that row ``b`` attends to — ``min(pos + 1, C)``
    for a row at position ``pos`` (a ring that has wrapped reads all of C),
    0 for an empty row, whose output is zeros.  A row reads
    ``ceil(lengths[b] / decode_block(C, K, D, kv_head_major))`` blocks of K
    and V, and no more: the stack stays in HBM and only those blocks are
    copied in, so XLA passes the stack to the kernel where it lies.  bf16
    operands, f32 scores, softmax and accumulation.  Returns (B, 1, H, D)."""
    b, _, h, d = q.shape
    if kv_head_major:
        _, _, n_kv, c, _ = k.shape
    else:
        _, _, c, n_kv, _ = k.shape
    blk = decode_block(c, n_kv, d, kv_head_major)
    if not blk:
        raise ValueError(f"decode_attention_pallas: a cache of (C, K, D) = {(c, n_kv, d)} "
                         f"cannot be copied in blocks (decode_block)")
    buf = (2, n_kv, blk, d) if kv_head_major else (2, blk, n_kv, d)
    kern = functools.partial(
        _decode_kernel, blk=blk, head_major=kv_head_major, group=h // n_kv,
        scale=scale or 1.0 / math.sqrt(d))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM(buf, k.dtype),
                pltpu.VMEM(buf, v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((b,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape(b, h, d), k, v)
    return out.reshape(b, 1, h, d)
