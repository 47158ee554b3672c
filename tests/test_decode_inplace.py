"""Decode writes each new token into the stacked KV cache in place.

``decode_stack`` keeps the whole cache stack in its scan carry: attention
writes the token's K/V at ``[layer, row, pos % C]`` and reads its layer by a
dynamic index.  The reference here is the copying form it replaced: slice
the layer's caches out of the stack, update the slice with
``apply_attn_decode``, put it back whole.  The two must agree bit for bit,
and the compiled step must hold no copy of a layer's K/V.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import shaped_instructions

from repro.configs import ALL_ARCHS, get_config
from repro.models import model as M
from repro.models.attention import apply_attn_decode
from repro.models.layers import apply_mlp, apply_norm
from repro.models.moe import apply_moe
from repro.models.ssm import apply_ssm_decode

B, S = 2, 16          # prefill batch and prompt length
CAPACITY = S + 4      # C = 20, or the window (16) where the config has one
STEPS = 3


def _at(tree, i):
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False), tree)


def _put(tree, sub, i):
    return jax.tree.map(
        lambda t, u: jax.lax.dynamic_update_index_in_dim(t, u.astype(t.dtype), i, 0), tree, sub)


def _ref_layer(xx, lp, cache, pos, cfg, kind):
    """One layer on its own sliced cache; returns the updated slice."""
    new = {}
    if kind in ("dense", "moe", "hybrid", "decoder"):
        xn = apply_norm(lp["ln1"], xx, cfg)
        h, kv = apply_attn_decode(lp["attn"], xn, {"k": cache["k"], "v": cache["v"]}, pos, cfg)
        new.update(kv)
        if kind == "hybrid":
            s_out, new["ssm"] = apply_ssm_decode(lp["ssm"], xn, cache["ssm"], cfg)
            h = (h + s_out) / 2.0
        xx = xx + h
    if kind == "ssm":
        y, new["ssm"] = apply_ssm_decode(lp["ssm"], apply_norm(lp["ln1"], xx, cfg), cache["ssm"], cfg)
        xx = xx + y
    if kind == "decoder":
        xn = apply_norm(lp["lnx"], xx, cfg)
        h, _ = apply_attn_decode(lp["xattn"], xn, {"k": cache["xk"], "v": cache["xv"]},
                                 pos, cfg, cross=True)
        new["xk"], new["xv"] = cache["xk"], cache["xv"]
        xx = xx + h
    if kind in ("dense", "hybrid", "decoder"):
        xx = xx + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], xx, cfg), cfg)
    if kind == "moe":
        y, _ = apply_moe(lp["moe"], apply_norm(lp["ln2"], xx, cfg), cfg)
        xx = xx + y
    return xx, new


def _ref_scan(stacked, x, caches, pos, cfg, kind, n):
    def layer(carry, lp_i):
        lp, i = lp_i
        xx, cs = carry
        xx, new = _ref_layer(xx, lp, _at(cs, i), pos, cfg, kind)
        return (xx, _put(cs, new, i)), None

    (x, caches), _ = jax.lax.scan(layer, (x, caches), (stacked, jnp.arange(n)))
    return x, caches


def _ref_decode_step(params, cfg, token, caches, pos):
    """``M.decode_step`` with every layer's cache sliced out and put back."""
    x = M._embed(params, token[:, None], cfg)
    if cfg.family == "vlm":
        period = cfg.cross_attn_period

        def group(carry, lp_i):
            lp, i = lp_i
            xx, cs = carry
            cache = _at(cs, i)
            xn = apply_norm(lp["xb"]["lnx"], xx, cfg)
            h, _ = apply_attn_decode(lp["xb"]["xattn"], xn, {"k": cache["xk"], "v": cache["xv"]},
                                     pos, cfg, cross=True)
            xx, inner = _ref_scan(lp["blocks"], xx + h, cache["inner"], pos, cfg, "dense", period)
            return (xx, _put(cs, {"xk": cache["xk"], "xv": cache["xv"], "inner": inner}, i)), None

        groups = cfg.n_layers // period
        (h, caches), _ = jax.lax.scan(
            group, (x, caches),
            ({"xb": params["xblocks"], "blocks": params["blocks"]}, jnp.arange(groups)))
    else:
        kind = {"encdec": "decoder"}.get(cfg.family, cfg.family)
        h, caches = _ref_scan(params["blocks"], x, caches, pos, cfg, kind, cfg.n_layers)
    h = apply_norm(params["ln_f"], h, cfg)
    return M.logits_fn(params, cfg, h)[:, 0], caches


def _windowed(arch):
    return bool(get_config(arch).window)


CASES = ([(a, "scalar") for a in ALL_ARCHS] + [(a, "per_row") for a in ALL_ARCHS]
         + [(a, "ring_wrap") for a in ALL_ARCHS if _windowed(a)])


@pytest.mark.parametrize("arch,pos_form", CASES)
def test_decode_in_place_matches_sliced_reference(arch, pos_form):
    cfg = get_config(arch).reduced().validate()
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab_size)
    modal = None
    if cfg.family in ("encdec", "vlm"):
        ml = cfg.num_modal_tokens if cfg.family == "vlm" else S
        modal = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (B, ml, cfg.d_model))
    logits, caches, pos = M.prefill(params, cfg, tokens, cache_capacity=CAPACITY, modal=modal)
    c = cfg.cache_len(CAPACITY)
    if pos_form == "per_row":
        pos = jnp.array([S, S - 5], jnp.int32)
    elif pos_form == "ring_wrap":
        assert c == cfg.window
        pos = jnp.array([2 * c + 3, c + 7], jnp.int32)   # both rows past the ring's end

    step = jax.jit(lambda p, t, cs, q: M.decode_step(p, cfg, t, cs, q))
    ref = jax.jit(lambda p, t, cs, q: _ref_decode_step(p, cfg, t, cs, q))
    got_caches, want_caches = caches, caches
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for n in range(STEPS):
        got, got_caches = step(params, tok, got_caches, pos + n)
        want, want_caches = ref(params, tok, want_caches, pos + n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=f"step {n}")
        for g, w in zip(jax.tree.leaves(got_caches), jax.tree.leaves(want_caches)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=f"step {n}")
        tok = jnp.argmax(want, -1).astype(jnp.int32)


def test_donated_decode_holds_no_copy_of_a_layer_cache():
    """olmo-1b widths, 2 layers, 4 slots x 256, bf16: the compiled step has
    no instruction (bitcasts aside) shaped like one layer's K or V.  The
    copying form had 20 of them at this size."""
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=2)
    b, c = 4, 256
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: M.init_cache(cfg, b, c))
    vec = jax.ShapeDtypeStruct((b,), jnp.int32)
    step = jax.jit(lambda p, t, cs, q: M.decode_step(p, cfg, t, cs, q), donate_argnums=(2,))
    text = step.lower(params, vec, caches, vec).compile().as_text()
    assert jax.tree.leaves(caches)[0].shape == (2, b, c, cfg.n_kv_heads, cfg.hd)
    assert shaped_instructions(text, (b, c, cfg.n_kv_heads, cfg.hd)) == []


@pytest.mark.parametrize("arch", ["olmo-1b", "starcoder2-15b"])
def test_empty_rows_leave_live_rows_bit_for_bit(arch):
    """The serve engine decodes a done row at position -1, an empty row that
    attention reads nothing for.  Live rows' logits and caches are what they
    were when a done row kept decoding at its own growing position (past
    the cache's end here), bit for bit; sequence-major MHA and a head-major
    grouped ring."""
    cfg = get_config(arch).reduced().validate()
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, S), 0, cfg.vocab_size)
    logits, caches, _ = M.prefill(params, cfg, tokens, cache_capacity=CAPACITY)
    c = cfg.cache_len(CAPACITY)
    pos = jnp.array([S, 2 * c + 1, S - 3, 5 * c], jnp.int32)
    done = np.array([False, True, False, True])
    step = jax.jit(lambda p, t, cs, q: M.decode_step(p, cfg, t, cs, q))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    before, after = caches, caches
    for n in range(STEPS):
        want, before = step(params, tok, before, pos + n)
        got, after = step(params, tok, after, jnp.where(done, -1, pos + n))
        np.testing.assert_array_equal(np.asarray(got)[~done], np.asarray(want)[~done],
                                      err_msg=f"step {n}")
        for g, w in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
            np.testing.assert_array_equal(np.asarray(g)[:, ~done], np.asarray(w)[:, ~done],
                                          err_msg=f"step {n}")
        tok = jnp.argmax(want, -1).astype(jnp.int32)
