"""Public attention op: MLOS-tunable implementation + block-shape dispatch.

``attention_settings`` is a registered smart component — its tunables
(impl / block_q / block_kv) are *auto-parameters* in the paper's sense: the
hash-table-bucket-count analogue for the TPU world.  They are structural
(class-b) tunables: changing them triggers re-jit, which the MLOS agent
treats as the paper's "costly re-initialization" parameter class.
"""
from __future__ import annotations

from typing import Optional

import jax

from ...core.configstore import bucket_pow2
from ...core.registry import MetricSpec, tunable_component
from ...core.tunable import Categorical, Int
from .. import require_tpu
from . import ref

__all__ = ["flash_attention", "decode_attention", "attention_settings",
           "AttentionKernelSettings", "workload_signature"]


@tunable_component(
    name="flash_attention",
    tunables=(
        Categorical("impl", default="unrolled",
                    choices=("naive", "scan", "unrolled", "unrolled_full", "pallas"),
                    description="attention algorithm / kernel path"),
        Int("block_q", default=512, low=128, high=2048, log=True, description="Q tile (MXU-aligned multiples of 128)"),
        Int("block_kv", default=512, low=128, high=2048, log=True, description="KV tile"),
    ),
    metrics=(
        MetricSpec("time_us", "d"),
        MetricSpec("hlo_flops", "d"),
        MetricSpec("hlo_bytes", "d"),
    ),
)
class AttentionKernelSettings:
    """Holder for the globally-tunable attention kernel configuration."""


attention_settings = AttentionKernelSettings()


def workload_signature(b: int, s_q: int, s_kv: int, d: int) -> str:
    """Bucketed call-shape signature — the workload axis of the config
    context.  Batch and sequence bucket at powers of two (a (b=2,s=512) call
    and a (b=8,s=4096) call are *different* workloads with their own tuned
    block sizes); head_dim is structural and kept exact."""
    return f"b{bucket_pow2(b)}q{bucket_pow2(s_q)}k{bucket_pow2(s_kv)}d{d}"


def _align(block: int, seq: int) -> int:
    block = min(block, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, q_offset: int = 0,
    impl: Optional[str] = None, block_q: Optional[int] = None, block_kv: Optional[int] = None,
    workload: Optional[str] = None,
) -> jax.Array:
    """Attention entry point used by the model; dispatches on tunables
    resolved for this call's workload context (shape-derived unless pinned
    via ``workload=``), falling back to the global singleton settings."""
    wl = workload or workload_signature(q.shape[0], q.shape[1], k.shape[1], q.shape[3])
    s = attention_settings.settings_for(wl)
    impl = impl or s["impl"]
    block_q = _align(block_q or s["block_q"], q.shape[1])
    block_kv = _align(block_kv or s["block_kv"], k.shape[1])
    if impl == "naive":
        return ref.naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    if impl == "scan":
        return ref.scan_attention(q, k, v, causal=causal, window=window, q_offset=q_offset, block_kv=block_kv)
    if impl in ("unrolled", "unrolled_full"):
        return ref.unrolled_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_kv=block_kv, exact_prefix=impl == "unrolled",
        )
    if impl == "pallas":
        require_tpu("flash_attention")
        from . import kernel

        return kernel.flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_kv=block_kv,
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0, kv_head_major: bool = False):
    return ref.decode_attention(q, k_cache, v_cache, pos, window=window,
                                kv_head_major=kv_head_major)
