"""Transformer stacks for all assigned families, built as scanned blocks.

Scan-over-layers with stacked parameters keeps the HLO O(1) in depth (a
95-layer model lowers as fast as a 2-layer one) — essential for the 80-cell
dry-run sweep on this container.  The remat policy applied to the scanned
block body is an MLOS auto-parameter (``stack_settings``).

Families:
  dense   norm→attn→res, norm→mlp→res
  moe     norm→attn→res, norm→moe→res (+aux loss accumulated through the scan)
  ssm     norm→mamba2→res
  hybrid  norm→(attn ∥ ssm: averaged)→res, norm→mlp→res   (Hymba)
  encdec  encoder stack (non-causal) + decoder stack with per-layer cross-attn
  vlm     outer scan over groups: cross-attn block then ``period`` self blocks
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.configstore import bucket_pow2
from ..core.registry import MetricSpec, tunable_component
from ..core.tunable import Categorical, Int
from ..parallel.sharding import constrain
from .attention import (apply_attn, apply_attn_decode, attn_params, cache_layout,
                        cross_attn_params)
from .config import ModelConfig
from .layers import P, apply_mlp, apply_norm, mlp_params, norm_params
from .moe import apply_moe, moe_params
from .ssm import apply_ssm, apply_ssm_decode, ssm_params

__all__ = [
    "stack_settings", "block_specs", "stack_specs", "forward_stack",
    "decode_stack", "prefill_stack", "remat_wrap", "stack_workload",
]


@tunable_component(
    name="layer_stack",
    tunables=(
        Categorical("remat", default="full", choices=("none", "dots", "full"),
                    description="activation-checkpoint policy for the scanned block"),
        Categorical("scan_layers", default=True, choices=(True, False),
                    description="lax.scan over layers vs python unroll"),
        Int("loss_chunk", default=2048, low=128, high=16384, log=True,
            description="sequence chunk for the cross-entropy head"),
    ),
    metrics=(MetricSpec("hlo_bytes", "d"), MetricSpec("time_us", "d")),
)
class StackSettings:
    pass


stack_settings = StackSettings()


def stack_workload(kind: str, b: int, s: int, n_layers: int) -> str:
    """Bucketed stack-call signature: family × batch × seq × depth.  A train
    pass at (b=8, s=4096) and a decode step at (b=1, s=1) resolve their own
    remat/scan/loss-chunk choices."""
    return f"{kind}_b{bucket_pow2(b)}s{bucket_pow2(s)}l{n_layers}"


# --------------------------------------------------------------------- specs
def block_specs(cfg: ModelConfig, kind: str = "auto") -> Dict[str, Any]:
    """P-spec tree for ONE layer of the given block kind."""
    kind = cfg.family if kind == "auto" else kind
    if kind in ("dense", "encoder"):
        return {"ln1": norm_params(cfg), "attn": attn_params(cfg),
                "ln2": norm_params(cfg), "mlp": mlp_params(cfg)}
    if kind == "moe":
        return {"ln1": norm_params(cfg), "attn": attn_params(cfg),
                "ln2": norm_params(cfg), "moe": moe_params(cfg)}
    if kind == "ssm":
        return {"ln1": norm_params(cfg), "ssm": ssm_params(cfg)}
    if kind == "hybrid":
        return {"ln1": norm_params(cfg), "attn": attn_params(cfg), "ssm": ssm_params(cfg),
                "ln2": norm_params(cfg), "mlp": mlp_params(cfg)}
    if kind == "decoder":  # enc-dec decoder layer
        return {"ln1": norm_params(cfg), "attn": attn_params(cfg),
                "lnx": norm_params(cfg), "xattn": cross_attn_params(cfg),
                "ln2": norm_params(cfg), "mlp": mlp_params(cfg)}
    if kind == "xblock":   # vlm cross-attention block
        return {"lnx": norm_params(cfg), "xattn": cross_attn_params(cfg)}
    raise ValueError(kind)


def stack_specs(specs: Dict[str, Any], n: int) -> Dict[str, Any]:
    """Add a leading ("layers",) axis to every leaf."""
    def add(p: P) -> P:
        return P((n, *p.shape), ("layers", *p.logical), p.init, p.scale)
    return jax.tree.map(add, specs, is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------- helpers
def _maybe_scan(body: Callable, carry: Any, xs: Any, length: int, *, scan: bool):
    """lax.scan, or a python unroll when scan=False (the dry-run's counter
    passes unroll so XLA cost analysis sees every iteration).  The stack
    entry points pass their context-resolved ``scan_layers`` value."""
    if scan:
        return jax.lax.scan(body, carry, xs, length=length)
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda t: t[i], xs) if xs is not None else None
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        return carry, jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    return carry, None


def remat_wrap(fn: Callable, policy: Optional[str] = None) -> Callable:
    policy = policy or stack_settings.settings_for("*")["remat"]
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full"


def _res(x: jax.Array) -> jax.Array:
    """Residual-stream sharding constraint (batch, seq, d_model)."""
    return constrain(x, ("batch", "seq", "d_model"))


def _mixer(lp: Dict[str, Any], x: jax.Array, cfg: ModelConfig, kind: str,
           xattn_src: Optional[jax.Array], q_offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    """One block body (train/prefill full-sequence). Returns (y, aux)."""
    aux = jnp.zeros((), jnp.float32)
    causal = kind != "encoder"
    if kind in ("dense", "encoder", "moe", "hybrid", "decoder"):
        h = apply_attn(lp["attn"], apply_norm(lp["ln1"], x, cfg), cfg,
                       causal=causal, q_offset=q_offset)
        if kind == "hybrid":
            s = apply_ssm(lp["ssm"], apply_norm(lp["ln1"], x, cfg), cfg)
            h = (h + s) / 2.0
        x = _res(x + h)
    if kind == "ssm":
        x = _res(x + apply_ssm(lp["ssm"], apply_norm(lp["ln1"], x, cfg), cfg))
    if kind == "decoder":
        x = _res(x + apply_attn(lp["xattn"], apply_norm(lp["lnx"], x, cfg), cfg, xkv=xattn_src))
    if kind in ("dense", "encoder", "hybrid", "decoder"):
        x = _res(x + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], x, cfg), cfg))
    if kind == "moe":
        y, aux = apply_moe(lp["moe"], apply_norm(lp["ln2"], x, cfg), cfg)
        x = _res(x + y)
    return x, aux


# ------------------------------------------------------------ train / encode
def forward_stack(
    stacked: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    kind: str = "auto",
    xattn_src: Optional[jax.Array] = None,
    n_layers: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence pass over a scanned stack. Returns (x, aux_loss_sum).

    For the vlm family, ``stacked`` is {"xblocks": (G,...), "blocks": (G,period,...)}.
    """
    kind = cfg.family if kind == "auto" else kind
    s = stack_settings.settings_for(stack_workload(kind, x.shape[0], x.shape[1], cfg.n_layers))

    if kind == "vlm":
        def group(carry, lp):
            xx, aux = carry
            xn = apply_norm(lp["xb"]["lnx"], xx, cfg)
            xx = _res(xx + apply_attn(lp["xb"]["xattn"], xn, cfg, xkv=xattn_src))
            xx, a2 = forward_stack(lp["blocks"], xx, cfg, kind="dense",
                                   n_layers=cfg.cross_attn_period)
            return (xx, aux + a2), None

        groups = cfg.n_layers // cfg.cross_attn_period
        (x, aux), _ = _maybe_scan(
            remat_wrap(group, s["remat"]), (x, jnp.zeros((), jnp.float32)),
            {"xb": stacked["xblocks"], "blocks": stacked["blocks"]}, groups,
            scan=s["scan_layers"])
        return x, aux

    def body(carry, lp):
        xx, aux = carry
        xx, a = _mixer(lp, xx, cfg, kind, xattn_src)
        return (xx, aux + a), None

    n = n_layers if n_layers is not None else (cfg.enc_layers if kind == "encoder" else cfg.n_layers)
    (x, aux), _ = _maybe_scan(remat_wrap(body, s["remat"]), (x, jnp.zeros((), jnp.float32)),
                              stacked, n, scan=s["scan_layers"])
    return x, aux


# ------------------------------------------------------------------- prefill
def prefill_stack(
    stacked: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    cache_capacity: int,
    *,
    kind: str = "auto",
    xattn_src: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Full-sequence pass that also fills per-layer decode state.

    Attention layers write K/V of the last ``cache_capacity`` positions; SSM
    layers carry (conv, ssd) state.  Returns (x, stacked_caches).
    """
    kind = cfg.family if kind == "auto" else kind
    sl = x.shape[1]
    cap = cfg.cache_len(cache_capacity)
    s_cfg = stack_settings.settings_for(stack_workload(kind, x.shape[0], sl, cfg.n_layers))

    def pad_kv(k: jax.Array) -> jax.Array:
        # keep last `cap` positions, left-pad if the sequence is shorter
        if k.shape[1] >= cap:
            return k[:, -cap:] if not cfg.window else _roll_ring(k, cap, sl)
        pad = jnp.zeros((k.shape[0], cap - k.shape[1], *k.shape[2:]), k.dtype)
        return jnp.concatenate([k, pad], axis=1)  # slots [0, sl) filled; pos continues at sl

    def _roll_ring(k: jax.Array, cap_: int, seq: int) -> jax.Array:
        # ring-buffer layout: token t lives at slot t % cap
        last = k[:, -cap_:]
        shift = seq % cap_
        return jnp.roll(last, shift, axis=1)

    def body(carry, lp):
        xx, aux = carry
        cache: Dict[str, Any] = {}
        if kind in ("dense", "moe", "hybrid", "decoder"):
            xn = apply_norm(lp["ln1"], xx, cfg)
            h, (k, v) = apply_attn(lp["attn"], xn, cfg, causal=True, return_kv=True)
            cache["k"], cache["v"] = cache_layout(pad_kv(k), cfg), cache_layout(pad_kv(v), cfg)
            if kind == "hybrid":
                s_out, sstate = apply_ssm(lp["ssm"], xn, cfg, return_state=True)
                h = (h + s_out) / 2.0
                cache["ssm"] = sstate
            xx = _res(xx + h)
        if kind == "ssm":
            y, sstate = apply_ssm(lp["ssm"], apply_norm(lp["ln1"], xx, cfg), cfg, return_state=True)
            cache["ssm"] = sstate
            xx = _res(xx + y)
        if kind == "decoder":
            xn = apply_norm(lp["lnx"], xx, cfg)
            h, (xk, xv) = apply_attn(lp["xattn"], xn, cfg, xkv=xattn_src, return_kv=True)
            cache["xk"], cache["xv"] = cache_layout(xk, cfg), cache_layout(xv, cfg)
            xx = _res(xx + h)
        if kind in ("dense", "hybrid", "decoder"):
            xx = _res(xx + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], xx, cfg), cfg))
        if kind == "moe":
            y, a = apply_moe(lp["moe"], apply_norm(lp["ln2"], xx, cfg), cfg)
            xx = _res(xx + y)
            aux = aux + a
        return (xx, aux), cache

    if kind == "vlm":
        def group(carry, lp):
            xx, aux = carry
            xn = apply_norm(lp["xb"]["lnx"], xx, cfg)
            h, (xk, xv) = apply_attn(lp["xb"]["xattn"], xn, cfg, xkv=xattn_src, return_kv=True)
            xx = _res(xx + h)
            (xx, a), inner = _maybe_scan(
                remat_wrap(body_dense, s_cfg["remat"]), (xx, jnp.zeros((), jnp.float32)),
                lp["blocks"], cfg.cross_attn_period, scan=s_cfg["scan_layers"])
            return (xx, aux + a), {"xk": cache_layout(xk, cfg), "xv": cache_layout(xv, cfg),
                                   "inner": inner}

        def body_dense(carry, lp):
            return body(carry, lp)

        saved_kind = kind
        kind = "dense"
        (x, aux), caches = _maybe_scan(
            remat_wrap(group, s_cfg["remat"]), (x, jnp.zeros((), jnp.float32)),
            {"xb": stacked["xblocks"], "blocks": stacked["blocks"]},
            cfg.n_layers // cfg.cross_attn_period, scan=s_cfg["scan_layers"])
        kind = saved_kind
        return x, caches

    (x, _aux), caches = _maybe_scan(remat_wrap(body, s_cfg["remat"]), (x, jnp.zeros((), jnp.float32)),
                                    stacked, cfg.n_layers, scan=s_cfg["scan_layers"])
    return x, caches


# -------------------------------------------------------------------- decode
def decode_stack(
    stacked: Dict[str, Any],
    x: jax.Array,                       # (B, 1, d)
    caches: Dict[str, Any],
    pos: jax.Array,
    cfg: ModelConfig,
    *,
    kind: str = "auto",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token pass over the layer stack.

    The cache stack rides in the scan CARRY (passing caches as scan xs→ys
    double-buffers the entire KV cache: measured +6.4 GB/device on
    deepseek-67B decode_32k) and never leaves it.  Each layer's attention
    writes the new token's K/V into the stack at ``[i, row, pos % C]`` and
    reads layer ``i`` by a dynamic index that feeds only the attention
    einsums; cross-attention caches are read the same way and not written.
    Only the small SSM state is sliced out and put back whole.  The vlm
    group path still slices each group's caches out and puts them back.
    """
    kind = cfg.family if kind == "auto" else kind
    scan = stack_settings.settings_for(
        stack_workload(kind, x.shape[0], x.shape[1], cfg.n_layers))["scan_layers"]

    def _at(tree, i):
        return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False), tree)

    def _put(tree, sub, i):
        return jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_index_in_dim(t, u.astype(t.dtype), i, 0),
            tree, sub)

    def ssm_step(lp, xn, cache, i):
        # the SSM state is KBs a layer: slicing it out and back costs nothing
        state = cache["ssm"] if i is None else _at(cache["ssm"], i)
        y, state = apply_ssm_decode(lp["ssm"], xn, state, cfg)
        cache["ssm"] = state if i is None else _put(cache["ssm"], state, i)
        return y

    def body(xx, lp, cache, i=None):
        """One layer.  With ``i``, ``cache`` is the whole stack and layer
        ``i`` is updated inside it; without, it is the layer's own cache."""
        cache = dict(cache)
        if kind in ("dense", "moe", "hybrid", "decoder"):
            xn = apply_norm(lp["ln1"], xx, cfg)
            h, kv = apply_attn_decode(lp["attn"], xn, {"k": cache["k"], "v": cache["v"]},
                                      pos, cfg, layer=i)
            cache.update(kv)
            if kind == "hybrid":
                h = (h + ssm_step(lp, xn, cache, i)) / 2.0
            xx = xx + h
        if kind == "ssm":
            xx = xx + ssm_step(lp, apply_norm(lp["ln1"], xx, cfg), cache, i)
        if kind == "decoder":
            xn = apply_norm(lp["lnx"], xx, cfg)
            h, _ = apply_attn_decode(lp["xattn"], xn, {"k": cache["xk"], "v": cache["xv"]},
                                     pos, cfg, cross=True, layer=i)
            xx = xx + h
        if kind in ("dense", "hybrid", "decoder"):
            xx = xx + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], xx, cfg), cfg)
        if kind == "moe":
            y, _ = apply_moe(lp["moe"], apply_norm(lp["ln2"], xx, cfg), cfg)
            xx = xx + y
        return xx, cache

    if kind == "vlm":
        # Still the copying form: each group's caches are sliced out of the
        # stack and put back whole, and so is each inner layer's.  No
        # benchmark cell serves a vlm yet.
        def group(carry, lp_i):
            lp, i = lp_i
            xx, cstack = carry
            cache = _at(cstack, i)
            xn = apply_norm(lp["xb"]["lnx"], xx, cfg)
            h, _ = apply_attn_decode(lp["xb"]["xattn"], xn,
                                     {"k": cache["xk"], "v": cache["xv"]}, pos, cfg, cross=True)
            xx = xx + h

            def inner(carry2, lp_j):
                lp2, j = lp_j
                xx2, inner_stack = carry2
                xx2, new_c = body(xx2, lp2, _at(inner_stack, j))
                return (xx2, _put(inner_stack, new_c, j)), None

            (xx, inner_stack), _ = _maybe_scan(
                inner, (xx, cache["inner"]),
                (lp["blocks"], jnp.arange(cfg.cross_attn_period)), cfg.cross_attn_period,
                scan=scan)
            cstack = _put(cstack, {"xk": cache["xk"], "xv": cache["xv"], "inner": inner_stack}, i)
            return (xx, cstack), None

        saved = kind
        kind = "dense"
        groups = cfg.n_layers // cfg.cross_attn_period
        (x, caches), _ = _maybe_scan(
            group, (x, caches),
            ({"xb": stacked["xblocks"], "blocks": stacked["blocks"]}, jnp.arange(groups)),
            groups, scan=scan)
        kind = saved
        return x, caches

    def layer(carry, lp_i):
        lp, i = lp_i
        xx, cstack = carry
        return body(xx, lp, cstack, i), None

    (x, caches), _ = _maybe_scan(layer, (x, caches),
                                 (stacked, jnp.arange(cfg.n_layers)), cfg.n_layers,
                                 scan=scan)
    return x, caches
