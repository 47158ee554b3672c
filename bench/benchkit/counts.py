"""Operations and bytes the algorithm needs, from shapes alone.

They count the work of the model as published, whatever implements it: the
real prompt tokens of a prefill and not its pads, attention over a request's
own live context and not over the whole cache, the head over the real
vocabulary, and for training forward plus backward (three times the
forward) with nothing recomputed.  Weights and the KV cache are bfloat16.
"""
from __future__ import annotations

from .reference import Arch

BYTES = 2   # bfloat16


def layer_matmul_params(a: Arch) -> int:
    """Weights that take part in a matrix product, over all layers."""
    attn = a.d * a.heads * a.hd * 2 + a.d * a.kv_heads * a.hd * 2
    mlp = a.d * a.f * (3 if a.mlp == "swiglu" else 2)
    return a.layers * (attn + mlp)


def head_params(a: Arch) -> int:
    return a.d * a.vocab


def attn_flops(a: Arch, ctx: int) -> float:
    """QK and PV products of one query against ``ctx`` keys, all layers."""
    c = min(ctx, a.window) if a.window else ctx
    return 4.0 * a.layers * a.heads * a.hd * c


def causal_attn_flops(a: Arch, n: int) -> float:
    """Sum of :func:`attn_flops` over the positions 1..n of one sequence."""
    if not a.window or n <= a.window:
        return 4.0 * a.layers * a.heads * a.hd * n * (n + 1) / 2
    w = a.window
    return 4.0 * a.layers * a.heads * a.hd * (w * (w + 1) / 2 + (n - w) * w)


def prefill_flops(a: Arch, n: int) -> float:
    """A prompt of ``n`` real tokens, logits for its last position."""
    return 2.0 * layer_matmul_params(a) * n + causal_attn_flops(a, n) + 2.0 * head_params(a)


def decode_flops(a: Arch, ctx: int) -> float:
    """One generated token whose attention sees ``ctx`` live tokens."""
    return 2.0 * (layer_matmul_params(a) + head_params(a)) + attn_flops(a, ctx)


def kv_bytes_per_token(a: Arch) -> int:
    return a.layers * 2 * a.kv_heads * a.hd * BYTES


def weight_bytes(a: Arch) -> int:
    """Weights one forward step reads: every layer's products and the head."""
    return (layer_matmul_params(a) + head_params(a)) * BYTES


def train_step_flops(a: Arch, batch: int, seq: int) -> float:
    """Forward and backward of one step over ``batch`` rows of ``seq``."""
    fwd = batch * (2.0 * (layer_matmul_params(a) + head_params(a)) * seq
                   + causal_attn_flops(a, seq))
    return 3.0 * fwd


def roofline_time(flops: float, bytes_: float, peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take: the larger of its two bounds."""
    return max(flops / peak_flops, bytes_ / peak_bw)
