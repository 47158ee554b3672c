"""Pallas TPU kernel for the Mamba-2 SSD primitive (chunked block form).

TPU-native adaptation: the chunk axis is the LAST grid dimension, which a
TPU core iterates sequentially — so the inter-chunk SSM state (P × N per
(batch, head)) is VMEM scratch carried across chunk steps, exactly like the
flash-attention online-softmax state.  Each chunk step does:

  intra:  y_i += (C_i·B_jᵀ ∘ L_ij) dt_j x_j      (chunk × chunk "attention")
  inter:  y_i += (C_i·state) ⊙ decay_in_i
  state:  state = e^{ΣΔA} state + Σ_j decay_out_j dt_j B_j ⊗ x_j

The chunk length is the MLOS auto-parameter (ops.py); MXU alignment wants
chunk and head_dim multiples of 128/8 respectively.

Validated against ref.ssd_naive_scan in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_pallas"]


def _kernel(x_ref, dt_ref, csc_ref, csr_ref, b_ref, c_ref, o_ref, state_ref, *,
            chunk: int, out_dtype):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)                 # (Q, P)
    dt = dt_ref[...]                                   # (Q, 1)
    cs = csc_ref[...]                                  # (Q, 1) in-chunk cumsum of dt·A
    cs_row = csr_ref[...]                              # (1, Q) the same, as a row
    bb = b_ref[...].astype(jnp.float32)                # (Q, N)
    cc = c_ref[...].astype(jnp.float32)                # (Q, N)

    # intra-chunk decay matrix L[i,j] = exp(cs_i - cs_j) for i >= j
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    el = jnp.exp(jnp.where(iq >= jq, cs - cs_row, -jnp.inf))   # (Q, Q)

    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * el
    dtx = dt * x                                       # (Q, P)
    y = jax.lax.dot_general(scores, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_i += decay_in_i * C_i · state   (state: (N, P))
    y = y + jnp.exp(cs) * jax.lax.dot_general(
        cc, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state' = e^{total} state + Σ_j decay_out_j B_jᵀ (dt_j x_j)
    total = csc_ref[chunk - 1, 0]                      # scalar: chunk's total
    decay_out = jnp.exp(total - cs)                    # (Q, 1)
    state_ref[...] = jnp.exp(total) * state_ref[...] + jax.lax.dot_general(
        bb * decay_out, dtx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (N, P)

    o_ref[...] = y.astype(out_dtype)


def ssd_pallas(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
    D: Optional[jax.Array] = None, *, chunk: int = 128,
    return_state: bool = False, interpret: bool = False,
):
    """Shapes as ref.ssd_chunked: x (B,S,H,P); dt (B,S,H); A (H,); B/C (B,S,G,N).

    Starts from a zero state (prefill).  ``interpret=True`` runs the Pallas
    interpreter (tests off the TPU)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    g = B.shape[2]
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    nc = s // chunk

    # Head-major layouts keep every block's two minor dims tile-aligned or
    # full: x/y (B,H,S,P) and dt (B,H,S,1) tile (chunk, P|1), the log-decay
    # cumsum (computed here, once per element) rides as a column and as a
    # row, and B/C fold their group axis into the last dim (N per group).
    xt = x.transpose(0, 2, 1, 3)
    dtf = dt.astype(jnp.float32).transpose(0, 2, 1)                  # (B,H,S)
    cs = jnp.cumsum((dtf * A.astype(jnp.float32)[None, :, None])
                    .reshape(b, h, nc, chunk), axis=-1)
    grid = (b, h, nc)
    sq = pl.Squeezed()
    kern = functools.partial(_kernel, chunk=chunk, out_dtype=x.dtype)
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, sq, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, sq, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, sq, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, sq, sq, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((sq, chunk, n), lambda bi, hi, ci: (bi, ci, hi // (h // g))),
            pl.BlockSpec((sq, chunk, n), lambda bi, hi, ci: (bi, ci, hi // (h // g))),
        ],
        out_specs=pl.BlockSpec((sq, sq, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xt, dtf.reshape(b, h, s, 1), cs.reshape(b, h, s, 1),
      cs.reshape(b, h, nc, 1, chunk), B.reshape(b, s, g * n), C.reshape(b, s, g * n))
    y = y.transpose(0, 2, 1, 3)

    if D is not None:
        y = y + (D[None, None, :, None] * x.astype(jnp.float32)).astype(y.dtype)
    if return_state:
        # final state is not emitted by the kernel; recompute via the ref path
        from . import ref

        _, state = ref.ssd_chunked(x, dt, A, B, C, None, chunk=chunk, return_state=True)
        return y, state
    return y
