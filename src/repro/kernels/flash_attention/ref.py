"""Pure-jnp oracles for flash attention (GQA + causal + sliding window).

Three reference implementations with different perf/fidelity trade-offs:

  * :func:`naive_attention` — materializes the full score matrix; the
    numerical oracle for everything else (small shapes only).
  * :func:`scan_attention` — lax.scan over KV blocks with online softmax;
    O(block) memory, but computes *masked* blocks too (≈2× causal FLOPs) —
    small HLO, fast compile.
  * :func:`unrolled_attention` — python-unrolled over Q blocks, slicing only
    the causally-needed KV prefix (exact causal FLOPs, larger HLO).

The choice is an MLOS tunable (see ops.py); the §Perf log shows the
compute-roofline effect.  All functions take
  q: (B, Sq, H, D), k/v: (B, Sk, K, D) with H % K == 0 (GQA)
and return (B, Sq, H, D).  ``q_offset`` positions q tokens at
``q_offset + arange(Sq)`` for decode/chunked-prefill.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["naive_attention", "scan_attention", "unrolled_attention", "decode_attention"]

_NEG_INF = -1e30


def _mask(qpos: jax.Array, kpos: jax.Array, causal: bool, window: int) -> jax.Array:
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), dtype=bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def _group_q(q: jax.Array, n_kv: int):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def naive_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True, window: int = 0,
    q_offset: int = 0, scale: Optional[float] = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    scale = scale or 1.0 / math.sqrt(d)
    qg = _group_q(q, n_kv)
    # bf16 operands + f32 accumulation (MXU-native); an explicit astype would
    # materialize full f32 operand copies in the lowered program
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(sk)
    s = jnp.where(_mask(qpos, kpos, causal, window)[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, h, d).astype(q.dtype)


def _online_block(carry, kb, vb, qg, kpos_b, qpos, causal, window, scale):
    """One online-softmax update. carry = (acc, m, l); shapes:
    acc (b,k,g,sq,d) f32; m,l (b,k,g,sq); kb/vb (b,blk,k,d)."""
    acc, m, l = carry
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    msk = _mask(qpos, kpos_b, causal, window)
    s = jnp.where(msk[None, None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
    return (acc, m_new, l)


def scan_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True, window: int = 0,
    q_offset: int = 0, scale: Optional[float] = None, block_kv: int = 512,
) -> jax.Array:
    """lax.scan over KV blocks with online softmax (masked blocks computed)."""
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    scale = scale or 1.0 / math.sqrt(d)
    block_kv = min(block_kv, sk)
    if sk % block_kv:
        raise ValueError(f"seq {sk} % block_kv {block_kv} != 0")
    g = h // n_kv
    qg = _group_q(q, n_kv)
    qpos = q_offset + jnp.arange(sq)
    nb = sk // block_kv
    kb = k.reshape(b, nb, block_kv, n_kv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block_kv, n_kv, d).transpose(1, 0, 2, 3, 4)

    def step(carry, inp):
        i, kblk, vblk = inp
        kpos_b = i * block_kv + jnp.arange(block_kv)
        return _online_block(carry, kblk, vblk, qg, kpos_b, qpos, causal, window, scale), None

    acc0 = jnp.zeros((b, n_kv, g, sq, d), jnp.float32)
    m0 = jnp.full((b, n_kv, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (jnp.arange(nb), kb, vb))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def unrolled_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True, window: int = 0,
    q_offset: int = 0, scale: Optional[float] = None, block_q: int = 1024, block_kv: int = 512,
    exact_prefix: bool = True,
) -> jax.Array:
    """Python-unrolled over Q blocks; each block attends only to its causal
    KV prefix (and window), so masked-out blocks are never computed —
    exact-FLOPs causal attention in pure jnp.

    ``exact_prefix=False`` computes the FULL KV range per Q block (masked
    blocks included) — the scan_attention FLOP semantics in unrolled form,
    used by the dry-run counter passes to cost the ``scan`` impl honestly."""
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    scale = scale or 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    if sq % block_q:
        raise ValueError(f"seq {sq} % block_q {block_q} != 0")
    outs = []
    for qi in range(sq // block_q):
        q0 = qi * block_q
        qblk = q[:, q0 : q0 + block_q]
        q_hi = q_offset + q0 + block_q  # one past the last q position in the block
        if causal and exact_prefix:
            k_hi = min(sk, q_hi)
        else:
            k_hi = sk
        k_lo = 0
        if window and exact_prefix:
            k_lo = max(0, q_offset + q0 - window + 1)
        # align to block_kv for tidy shapes
        k_lo = (k_lo // block_kv) * block_kv
        k_hi = min(sk, ((k_hi + block_kv - 1) // block_kv) * block_kv)
        kblk = k[:, k_lo:k_hi]
        vblk = v[:, k_lo:k_hi]
        o = naive_attention(
            qblk, kblk, vblk, causal=causal, window=window,
            q_offset=q_offset + q0 - k_lo, scale=scale,
        )
        outs.append(o)
    return jnp.concatenate(outs, axis=1)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array, *,
    scale: Optional[float] = None, kv_head_major: bool = False,
) -> jax.Array:
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, D); caches: (B, C, K, D) where C = cache capacity, or
    (B, K, C, D) with ``kv_head_major``.
    ``pos`` — int32, scalar or per-row ``(B,)``: number of tokens already in
    context (0-based index of the current token).  A vector ``pos`` gives
    every batch row its own validity horizon — the continuous-batching case
    where each slot decodes at its own sequence position.  Row ``b`` attends
    to slots ``[0, min(pos + 1, C))``: a windowed cache (C == window) is a
    ring buffer indexed ``t % C``, all of it valid once ``pos >= C``.  A
    negative ``pos`` is an empty row: it attends to nothing and its output
    is zeros.
    """
    if kv_head_major:
        b, n_kv, c, d = k_cache.shape
        kv = "bksd"
    else:
        b, c, n_kv, d = k_cache.shape
        kv = "bskd"
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    qg = _group_q(q, n_kv)  # (b,1,k,g,d)
    s = jnp.einsum(f"bqkgd,{kv}->bkgqs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(c)
    # (1,1) for scalar pos, (B,1) per-row: one mask expression serves both.
    pos_r = jnp.atleast_1d(pos)[:, None]
    valid = slot[None, :] <= pos_r  # all of a wrapped ring: slot < c <= pos
    s = jnp.where(valid[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p.astype(v_cache.dtype)
    if kv_head_major:
        # batch dims leading in the output: the form XLA's CPU backend also
        # runs in bfloat16 (q is one token, so the move costs nothing)
        o = jnp.moveaxis(jnp.einsum("bkgqs,bksd->bkgqd", p, v_cache,
                                    preferred_element_type=jnp.float32), 3, 1)
    else:
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache, preferred_element_type=jnp.float32)
    o = jnp.where(pos_r[:, :, None, None] >= 0, o.reshape(b, 1, q.shape[2], d), 0)
    return o.astype(q.dtype)
