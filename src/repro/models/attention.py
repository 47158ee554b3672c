"""Attention block: projections + rope + (self|cross) attention + KV caches.

Builds on :mod:`repro.kernels.flash_attention` for the core computation so the
MLOS-tunable impl/block knobs apply uniformly to every architecture.

Conventions:
  * activations x: (B, S, d_model); q/k/v: (B, S, H|K, hd)
  * KV cache per layer: dict(k=(B, C, K, hd), v=(B, C, K, hd)); capacity
    C = cfg.cache_len(context) — a ring buffer when C == window.  Grouped KV
    heads (K < H) are stored head-major instead, (B, K, C, hd)
    (:func:`kv_head_major`).  Decode may instead take the stacked leaves
    (L, B, ...) and a layer index.
  * ``pos`` is a scalar int32 = number of tokens already consumed.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import ops as attn_ops
from ..parallel.sharding import active_rules, constrain, spec_for
from .config import ModelConfig
from .layers import P, rope

__all__ = ["attn_params", "cross_attn_params", "attn_cache_spec", "kv_head_major",
           "cache_layout", "apply_attn", "apply_attn_decode"]


def attn_params(cfg: ModelConfig, cross: bool = False) -> Dict[str, P]:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    wo_scale = 1.0 / math.sqrt(2 * max(cfg.n_layers, 1))
    out = {
        "wq": P((d, h, hd), ("d_model", "heads", "head_dim")),
        "wk": P((d, k, hd), ("d_model", "kv_heads", "head_dim")),
        "wv": P((d, k, hd), ("d_model", "kv_heads", "head_dim")),
        "wo": P((h, hd, d), ("heads", "head_dim", "d_model"), scale=wo_scale),
    }
    if cfg.use_bias:
        out["bq"] = P((h, hd), ("heads", "head_dim"), "zeros")
        out["bk"] = P((k, hd), ("kv_heads", "head_dim"), "zeros")
        out["bv"] = P((k, hd), ("kv_heads", "head_dim"), "zeros")
        out["bo"] = P((d,), ("d_model",), "zeros")
    if cfg.qk_norm and not cross:
        out["q_norm"] = P((hd,), ("head_dim",), "ones")
        out["k_norm"] = P((hd,), ("head_dim",), "ones")
    return out


def cross_attn_params(cfg: ModelConfig) -> Dict[str, P]:
    return attn_params(cfg, cross=True)


def kv_head_major(cfg: ModelConfig) -> bool:
    """Whether the KV cache is stored (B, K, C, hd) rather than (B, C, K, hd).

    Grouped heads make decode's two attention products batched dots over
    (B, K).  XLA's TPU compiler feeds such a dot a layer of the stacked
    cache where it lies only when K is ahead of C; in the (B, C, K, hd)
    order it first copies the layer's K and V out of the stack, every step.
    One query per KV head (olmo-1b) compiles to multiply-reduces that read
    the sequence-major layer in place, and keeps it: stored head-major, its
    products become dots, which changes its TPU program, and XLA's CPU
    backend then copies each layer's bfloat16 V to float32 every step
    (``tests/test_decode_inplace.py`` finds two such buffers)."""
    return cfg.n_kv_heads < cfg.n_heads


def cache_layout(kv: jax.Array, cfg: ModelConfig) -> jax.Array:
    """K or V of a sequence, (B, S, K, hd), in the cache's order."""
    return jnp.swapaxes(kv, 1, 2) if kv_head_major(cfg) else kv


def attn_cache_spec(cfg: ModelConfig, batch: int, context: int) -> Dict[str, P]:
    """Per-layer KV-cache leaf specs (stacked over layers by the caller)."""
    c = cfg.cache_len(context)
    if kv_head_major(cfg):
        shape = (batch, cfg.n_kv_heads, c, cfg.hd)
        logical = ("batch", "kv_heads", "cache_seq", "head_dim")
    else:
        shape = (batch, c, cfg.n_kv_heads, cfg.hd)
        logical = ("batch", "cache_seq", "kv_heads", "head_dim")
    return {"k": P(shape, logical, "zeros"), "v": P(shape, logical, "zeros")}


def _heads_or_seq(x: jax.Array, heads_name: str) -> tuple:
    """Logical axes for an activation (B,S,H,D): head-parallel if H divides
    the model axis, else sequence-parallel (never replicated)."""
    head_first = ("batch", None, heads_name, None)
    mesh, rules = active_rules()
    if mesh is None or rules is None:
        return head_first
    s = spec_for(P(tuple(x.shape), head_first), rules, mesh)
    if s[2] is not None:
        return head_first
    return ("batch", "seq", None, None)


def _qk_rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(params: Dict[str, jax.Array], x: jax.Array, xkv: jax.Array, cfg: ModelConfig,
                 *, use_rope: bool, q_positions: Optional[jax.Array], kv_positions: Optional[jax.Array]):
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dke->bske", xkv, params["wk"])
    v = jnp.einsum("bsd,dke->bske", xkv, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if "q_norm" in params:
        q = _qk_rmsnorm(q, params["q_norm"])
        k = _qk_rmsnorm(k, params["k_norm"])
    if use_rope:
        q = rope(q, q_positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def apply_attn(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    xkv: Optional[jax.Array] = None,        # cross-attention source (enc output / modal embeds)
    causal: bool = True,
    use_rope: bool = True,
    q_offset: int = 0,
    return_kv: bool = False,
) -> Any:
    """Full-sequence attention (train / prefill).  Returns y (+ (k, v) for cache fill)."""
    b, s, _ = x.shape
    cross = xkv is not None
    src = xkv if cross else x
    qpos = q_offset + jnp.arange(s)
    kpos = jnp.arange(src.shape[1])
    q, k, v = _project_qkv(params, x, src, cfg, use_rope=use_rope and not cross,
                           q_positions=qpos, kv_positions=kpos)
    # Megatron-SP transition: residual is sequence-sharded; attention runs
    # head-parallel with the sequence gathered ONCE per layer (bf16), not
    # per-block — these constraints stop GSPMD re-resharding inside the
    # attention loop (measured 6 GB/layer → ~0.5 GB/layer, §Perf).
    # Archs whose head count doesn't divide the model axis (hymba: 25H/5KV)
    # fall back to SEQUENCE-parallel attention: q rows stay seq-sharded,
    # K/V gather (each device computes its own query rows).
    q_log = _heads_or_seq(q, "heads")
    q = constrain(q, q_log)
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    y = attn_ops.flash_attention(
        q, k, v, causal=causal and not cross, window=0 if cross else cfg.window, q_offset=q_offset
    )
    y = constrain(y, q_log)
    y = jnp.einsum("bshe,hed->bsd", y, params["wo"])
    if "bo" in params:
        y = y + params["bo"]
    if return_kv:
        return y, (k, v)
    return y


def apply_attn_decode(
    params: Dict[str, jax.Array],
    x: jax.Array,                            # (B, 1, d_model)
    cache: Dict[str, jax.Array],
    pos: jax.Array,                          # int32 scalar or (B,): index of current token
    cfg: ModelConfig,
    *,
    cross: bool = False,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token attention against (and update of) a KV cache.

    For self-attention the new token's K/V are written at slot ``pos % C``
    (ring buffer when C == window).  ``pos`` may be a scalar (gang-scheduled
    decode: all rows share one position) or per-row ``(B,)`` (continuous
    batching: each slot carries its own position, rope phase and validity
    horizon).  A negative per-row ``pos`` is an empty row: attention reads
    nothing for it and returns zeros, and its write lands in slot ``C - 1``
    of its own row (the serve engine overwrites a row whole before reusing
    it).  Cross-attention caches are static (pre-filled from the
    encoder/modal source) and not updated.

    With ``layer``, the cache leaves are the whole layer stack
    ``(L, B, ...)``: the token is written into layer ``layer`` of the stack
    itself and attention reads that layer where it lies, so no copy of the
    layer's cache is made (the stack is the scan's donated carry).
    """
    head_major = kv_head_major(cfg)
    c = cache["k"].shape[-2 if head_major else -3]
    if cross:
        q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
        if "bq" in params:
            q = q + params["bq"]
        q = constrain(q, ("batch", None, None, None))
        y = attn_ops.decode_attention(q, cache["k"], cache["v"], jnp.asarray(c - 1, jnp.int32),
                                      kv_head_major=head_major, layer=layer)
    else:
        per_row = pos.ndim == 1
        q, k, v = _project_qkv(
            params, x, x, cfg, use_rope=True,
            q_positions=pos[:, None] if per_row else pos[None],
            kv_positions=pos[:, None] if per_row else pos[None],
        )
        # decode: q is tiny — replicate heads over `model`; the KV cache is
        # sequence-sharded there, so attention runs as sharded partial
        # softmax + small psum (distributed flash-decode), never gathering
        # the cache.
        q = constrain(q, ("batch", None, None, None))
        k = constrain(k, ("batch", None, None, None))
        v = constrain(v, ("batch", None, None, None))
        slot = (pos % c).astype(jnp.int32)
        lead = () if layer is None else (layer,)
        if per_row:
            rows = jnp.arange(x.shape[0])
            # head-major: every index broadcast to (B, K), so the scatter's
            # indexed dims are adjacent and it stays in place (a slice
            # between them makes XLA copy the whole stack)
            at = ((rows[:, None], jnp.arange(cfg.n_kv_heads)[None, :], slot[:, None])
                  if head_major else (rows, slot))

            def write(t: jax.Array, u: jax.Array) -> jax.Array:
                return t.at[(*lead, *at)].set(u[:, 0].astype(t.dtype))
        else:
            def write(t: jax.Array, u: jax.Array) -> jax.Array:
                u = cache_layout(u, cfg).astype(t.dtype)
                u = u.reshape((1,) * len(lead) + u.shape)
                return jax.lax.dynamic_update_slice(t, u, (*lead, 0, 0, slot, 0) if head_major
                                                    else (*lead, 0, slot, 0, 0))
        cache = dict(k=write(cache["k"], k), v=write(cache["v"], v))
        y = attn_ops.decode_attention(q, cache["k"], cache["v"], pos,
                                      kv_head_major=head_major, layer=layer)
    y = jnp.einsum("bshe,hed->bsd", y, params["wo"])
    if "bo" in params:
        y = y + params["bo"]
    return y, cache
