"""Drive olmo-1b through its serve and train entry points once on one TPU
chip, at published widths, and check what comes out.

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process).  Each
phase prints its lines and either passes or raises; any raise exits
non-zero and no result line is printed.

  1. device   — JAX's first device must be a TPU: JAX falls back to the CPU
                 on its own when the TPU fails to start.
  2. serve    — ``BatchedServer`` (continuous batching) over random bf16
                 olmo-1b weights answers 16 seeded prompts of 100-500 tokens.
  3. train    — ``run_training`` takes 3 steps of olmo-1b at published
                 widths, its depth cut to 4 layers so the state fits 16 GB.
  4. kernel   — the Pallas flash-attention kernel, compiled for the chip,
                 agrees with the float32 reference.

The last line of stdout is ``{"ok": true, "device": {...}}``.  No phase
measures speed.  The phases take the config and shapes as arguments, so
``tests/test_chip_smoke.py`` rehearses them on the CPU at ``reduced()`` size.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import sys
from pathlib import Path
from typing import Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import configstore  # noqa: E402
from repro.kernels.flash_attention import ops as attn_ops  # noqa: E402
from repro.kernels.flash_attention import ref as attn_ref  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention_pallas  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.runtime.serve_loop import BatchedServer  # noqa: E402
from repro.runtime.train_loop import run_training  # noqa: E402

ARCH = "olmo-1b"


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def device_check() -> jax.Device:
    """The first device, if it is a TPU; otherwise exit naming what JAX found."""
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)}",
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's first device is on platform "
                         f"{dev.platform!r}")
    return dev


def serve_phase(cfg: ModelConfig, *, capacity: int, max_batch: int, max_new_tokens: int,
                n_requests: int, prompt_lens: Tuple[int, int], seed: int = 0) -> None:
    """Serve ``n_requests`` seeded prompts through the continuous engine."""
    dev = jax.devices()[0]
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    srv = BatchedServer(params, cfg, capacity=capacity,
                        settings={"max_batch": max_batch, "max_new_tokens": max_new_tokens})
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    rids = [srv.submit(rng.integers(0, cfg.vocab_size, size=int(n), dtype=np.int32))
            for n in lens]

    # The attention settings each prefill width class resolves, and where
    # they come from: the store's fallback chain relaxes hardware, so an
    # entry tuned elsewhere must show itself.
    here = configstore.hardware_fingerprint()
    for width in sorted({configstore.bucket_pow2(int(n)) for n in lens}):
        wl = attn_ops.workload_signature(1, width, width, cfg.hd)
        entry = configstore.default_store().resolve_entry(
            configstore.context_for("flash_attention", wl))
        origin = "defaults"
        if entry is not None:
            hw = entry["context"]["hardware"]
            origin = f"store entry tuned on {hw}" + ("" if hw == here else
                                                     f", NOT this hardware ({here})")
        print(f"serve: flash_attention@{wl} = "
              f"{dict(attn_ops.attention_settings.settings_for(wl))} from {origin}",
              flush=True)

    metrics = srv.run()
    done = [srv.results[rid] for rid in rids if rid in srv.results]
    _require(len(done) == n_requests,
             f"{len(done)} of {n_requests} requests completed")
    for r in done:
        _require(1 <= len(r.tokens) <= r.eff_budget,
                 f"request {r.rid}: {len(r.tokens)} tokens, budget {r.eff_budget}")
        toks = np.asarray(r.tokens)
        _require(bool((toks >= 0).all() and (toks < cfg.vocab_size).all()),
                 f"request {r.rid}: token ids outside [0, {cfg.vocab_size})")
    caches = jax.tree.leaves(srv._caches)
    cache_devs = {d for leaf in caches for d in leaf.devices()}
    _require(cache_devs == {dev}, f"KV caches on {cache_devs}, not on {dev}")
    cache_gib = sum(leaf.nbytes for leaf in caches) / 2**30
    print(f"serve: {cfg.name} d_model {cfg.d_model} x {cfg.n_layers} layers, "
          f"{n_requests} requests of {int(lens.min())}-{int(lens.max())} prompt tokens "
          f"completed with {int(metrics['total_tokens'])} tokens in "
          f"{int(metrics['decode_steps'])} decode steps, {int(metrics['decode_syncs'])} "
          f"host syncs; KV cache {cache_gib:.2f} GiB on {dev}", flush=True)


def train_phase(cfg: ModelConfig, *, n_layers: int, global_batch: int, seq_len: int,
                n_steps: int, seed: int = 0) -> None:
    """``n_steps`` of ``run_training`` at ``cfg``'s widths, depth cut to ``n_layers``."""
    cut = dataclasses.replace(cfg, n_layers=n_layers).validate()
    print(f"train: depth cut {cfg.n_layers} -> {n_layers} layers; widths as published "
          f"(d_model {cfg.d_model}, {cfg.n_heads} heads of {cfg.hd}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size})", flush=True)
    out = run_training(cut, n_steps=n_steps, global_batch=global_batch, seq_len=seq_len,
                       ckpt_dir=None, seed=seed)
    losses = [h["loss"] for h in out["history"]]
    _require(len(losses) == n_steps, f"{len(losses)} of {n_steps} steps ran")
    _require(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    print(f"train: {n_steps} steps at batch {global_batch} x seq {seq_len}, "
          f"losses {losses}", flush=True)


def kernel_phase(shape: Tuple[int, int, int, int], *, block: int,
                 interpret: bool = False, seed: int = 0) -> None:
    """Causal bf16 Pallas flash attention vs the float32 naive reference."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
               for key in keys)
    fn = jax.jit(functools.partial(flash_attention_pallas, causal=True, block_q=block,
                                   block_kv=block, interpret=interpret))
    compiled = fn.lower(q, k, v).compile()
    lowered_for_chip = "tpu_custom_call" in compiled.as_text()
    _require(interpret or lowered_for_chip,
             "compiled flash attention holds no tpu_custom_call: the kernel did not "
             "lower for the chip")
    out = compiled(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = attn_ref.naive_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                        causal=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    _require(err <= 2e-2, f"flash attention max |err| {err} > 2e-2")
    print(f"kernel: flash_attention_pallas {shape} bf16 causal, blocks {block}, "
          f"{'interpreted' if interpret else 'compiled'} (tpu_custom_call: "
          f"{lowered_for_chip}); max |err| vs float32 naive {err}", flush=True)


def main() -> int:
    dev = device_check()
    cfg = get_config(ARCH)
    serve_phase(cfg, capacity=2048, max_batch=8, max_new_tokens=32, n_requests=16,
                prompt_lens=(100, 500))
    train_phase(cfg, n_layers=4, global_batch=4, seq_len=1024, n_steps=3)
    kernel_phase((1, 2048, 16, 128), block=512)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
