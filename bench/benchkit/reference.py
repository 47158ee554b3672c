"""The plain reference of the dense decoder family, in float32 ``jax.numpy``.

It follows the published descriptions the configuration files cite (OLMo:
non-parametric LayerNorm, SwiGLU, RoPE, no biases, tied head; StarCoder2:
LayerNorm with bias, biased attention and GELU-tanh MLP, RoPE, grouped KV
heads, a sliding window) with no kernel, cache, batching or padding trick.
It imports nothing of the program.  The weights it reads are the ones
:func:`make_params` draws from the seed, in the layer layout the program
takes them in (stacked over layers, vocabulary rows padded to a multiple of
256, only the first ``vocab_size`` of them used).

``quant="fp8"`` is the control: every weight matrix product takes its two
operands, in the forward and the backward, through float8 e4m3's 4-bit
significand, the step a later change could be tempted to take under a
bfloat16 configuration.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes and mechanisms of one configuration file (HF key names)."""

    d: int
    f: int
    layers: int
    heads: int
    kv_heads: int
    vocab: int
    rope_theta: float
    norm: str                 # "layernorm_np" | "layernorm"
    mlp: str                  # "swiglu" | "gelu_tanh"
    bias: bool
    tied: bool
    window: int
    eps: float

    @property
    def hd(self) -> int:
        return self.d // self.heads

    @property
    def vocab_rows(self) -> int:
        return (self.vocab + 255) // 256 * 256

    @classmethod
    def from_file(cls, c: Dict[str, Any]) -> "Arch":
        act = c["hidden_act"]
        if act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(f"reference has no MLP for hidden_act {act!r}")
        return cls(d=c["hidden_size"], f=c["intermediate_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   norm="layernorm" if c.get("norm_type") == "layer_norm" else "layernorm_np",
                   mlp="swiglu" if act == "silu" else "gelu_tanh",
                   bias=bool(c.get("use_bias", False)),
                   tied=bool(c["tie_word_embeddings"]),
                   window=int(c.get("sliding_window") or 0),
                   eps=float(c.get("norm_epsilon", 1e-5)))


# ------------------------------------------------------------------ weights
def param_shapes(a: Arch) -> Dict[str, Any]:
    """Leaf shapes and fan-ins, in the program's layout: {path: (shape, std)}."""
    L, d, f, h, k, hd = a.layers, a.d, a.f, a.heads, a.kv_heads, a.hd
    deep = 1.0 / math.sqrt(2 * L)
    out: Dict[str, Any] = {"embed": ((a.vocab_rows, d), 1 / math.sqrt(d))}
    if not a.tied:
        out["out"] = ((d, a.vocab_rows), 1 / math.sqrt(d))
    out.update({
        "blocks/attn/wq": ((L, d, h, hd), 1 / math.sqrt(d)),
        "blocks/attn/wk": ((L, d, k, hd), 1 / math.sqrt(d)),
        "blocks/attn/wv": ((L, d, k, hd), 1 / math.sqrt(d)),
        "blocks/attn/wo": ((L, h, hd, d), deep / math.sqrt(h * hd)),
    })
    if a.bias:
        out.update({"blocks/attn/bq": ((L, h, hd), 0.02), "blocks/attn/bk": ((L, k, hd), 0.02),
                    "blocks/attn/bv": ((L, k, hd), 0.02), "blocks/attn/bo": ((L, d), 0.02)})
    if a.mlp == "swiglu":
        out.update({"blocks/mlp/wi_gate": ((L, d, f), 1 / math.sqrt(d)),
                    "blocks/mlp/wi_up": ((L, d, f), 1 / math.sqrt(d)),
                    "blocks/mlp/wo": ((L, f, d), deep / math.sqrt(f))})
    else:
        out.update({"blocks/mlp/wi": ((L, d, f), 1 / math.sqrt(d)),
                    "blocks/mlp/bi": ((L, f), 0.02),
                    "blocks/mlp/wo": ((L, f, d), deep / math.sqrt(f)),
                    "blocks/mlp/bo": ((L, d), 0.02)})
    if a.norm == "layernorm":
        for ln in ("blocks/ln1", "blocks/ln2"):
            out[f"{ln}/scale"] = ((L, d), -0.02)      # negative std: 1 + noise
            out[f"{ln}/bias"] = ((L, d), 0.02)
        out["ln_f/scale"] = ((d,), -0.02)
        out["ln_f/bias"] = ((d,), 0.02)
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    if "ln_f" not in tree:
        tree["ln_f"] = {}
    for ln in ("ln1", "ln2"):
        tree["blocks"].setdefault(ln, {})
    return tree


def make_params(a: Arch, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Weights from ``seed``, drawn on the device in one jitted call."""
    shapes = param_shapes(a)

    @jax.jit
    def draw(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            flat[path] = (1 + abs(std) * z) if std < 0 else std * z
            flat[path] = flat[path].astype(dtype)
        return _nest(flat)

    return draw(seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**64 (seeds may pass 2**32)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


# ------------------------------------------------------------------ algebra
def _f8(x: jax.Array) -> jax.Array:
    """float8 e4m3's precision: the significand rounded to 4 bits."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


@functools.lru_cache(maxsize=None)
def _fp8_product(spec: str):
    """``einsum(spec)`` with float8 operands in the forward and the backward."""
    plain = lambda x, w: jnp.einsum(spec, x, w, precision=HI)

    @jax.custom_vjp
    def product(x, w):
        return plain(_f8(x), _f8(w))

    def fwd(x, w):
        xq, wq = _f8(x), _f8(w)
        return plain(xq, wq), (xq, wq)

    def bwd(res, g):
        return jax.vjp(plain, *res)[1](_f8(g))

    product.defvjp(fwd, bwd)
    return product


def _i8(t: jax.Array, axes: Tuple[int, ...]) -> jax.Array:
    """Symmetric int8: one scale (absmax / 127) per slice over ``axes``."""
    s = jnp.max(jnp.abs(t), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(t / s), -127, 127) * s


def _int8_product(spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``einsum(spec)`` as the MXU's int8 path computes it: the activations
    with a scale per token, the weights with a scale per output channel,
    each over the contracted axes.  Forward only (serving)."""
    ins, out = spec.split("->")
    lhs, rhs = ins.split(",")
    red = set(lhs) & set(rhs) - set(out)
    xq = _i8(x, tuple(i for i, c in enumerate(lhs) if c in red))
    wq = _i8(w, tuple(i for i, c in enumerate(rhs) if c in red))
    return jnp.einsum(spec, xq, wq, precision=HI)


def mm(spec: str, x: jax.Array, w: jax.Array, quant: str = "") -> jax.Array:
    """A weight product in float32 at full precision; for a control, with
    float8 operands in the forward and backward (``quant="fp8"``) or int8
    operands in the forward (``quant="int8"``)."""
    if quant == "fp8":
        return _fp8_product(spec)(x, w)
    if quant == "int8":
        return _int8_product(spec, x, w)
    return jnp.einsum(spec, x, w, precision=HI)


def norm(p: Dict[str, jax.Array], x: jax.Array, a: Arch) -> jax.Array:
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + a.eps)
    if a.norm == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate halves (GPT-NeoX layout). x: (B, S, H, hd); pos: (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p: Dict[str, Any], x: jax.Array, a: Arch, quant: str = "") -> jax.Array:
    """One decoder layer over (B, S, d), causal from position 0."""
    b, s, _ = x.shape
    pos = jnp.arange(s)
    xn = norm(p.get("ln1", {}), x, a)
    at = p["attn"]
    q = mm("bsd,dhe->bshe", xn, at["wq"], quant)
    k = mm("bsd,dke->bske", xn, at["wk"], quant)
    v = mm("bsd,dke->bske", xn, at["wv"], quant)
    if a.bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    g = a.heads // a.kv_heads
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=HI) / math.sqrt(a.hd)
    ok = pos[:, None] >= pos[None, :]
    if a.window:
        ok &= pos[:, None] - pos[None, :] < a.window
    sc = jnp.where(ok, sc, NEG)
    y = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), v, precision=HI)
    y = mm("bshe,hed->bsd", y, at["wo"], quant)
    if a.bias:
        y = y + at["bo"]
    x = x + y
    xn = norm(p.get("ln2", {}), x, a)
    m = p["mlp"]
    if a.mlp == "swiglu":
        hmid = jax.nn.silu(mm("bsd,df->bsf", xn, m["wi_gate"], quant)) * \
            mm("bsd,df->bsf", xn, m["wi_up"], quant)
        y = mm("bsf,fd->bsd", hmid, m["wo"], quant)
    else:
        hmid = jax.nn.gelu(mm("bsd,df->bsf", xn, m["wi"], quant) + m["bi"], approximate=True)
        y = mm("bsf,fd->bsd", hmid, m["wo"], quant) + m["bo"]
    return x + y


def head(params: Dict[str, Any], x: jax.Array, a: Arch, quant: str = "") -> jax.Array:
    """Final norm and logits over the real vocabulary (float32)."""
    f32 = lambda t: t.astype(jnp.float32)
    xn = norm(jax.tree.map(f32, params.get("ln_f", {})), x, a)
    if a.tied:
        w = f32(params["embed"][: a.vocab]).T
    else:
        w = f32(params["out"][:, : a.vocab])
    return mm("bsd,dv->bsv", xn, w, quant)


def _layer_params(blocks: Dict[str, Any], i: jax.Array) -> Dict[str, Any]:
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False).astype(jnp.float32),
                        blocks)


# ------------------------------------------------------------------ serving
@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _embed(params, toks, a: Arch, quant: str = ""):
    return params["embed"][toks].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _layer_at(blocks, i, x, a: Arch, quant: str = ""):
    return layer(_layer_params(blocks, i), x, a, quant)


def _logits_rows(params, x, lo: int, n: int, a: Arch, quant: str):
    return _logits_jit(params, x, jnp.asarray(lo, jnp.int32), a=a, quant=quant, n=n)


_logits_jit = jax.jit(lambda params, x, lo, a, quant, n: head(
    params, jax.lax.dynamic_slice_in_dim(x, lo, n, axis=1), a, quant),
    static_argnames=("a", "quant", "n"))


def served_gaps(params: Dict[str, Any], a: Arch, seq: np.ndarray, first: int,
                served: np.ndarray, quant: str = "", pad_to: int = 0) -> np.ndarray:
    """Gap of each served token below the reference's best logit.

    ``seq`` is the token sequence the engine ran (left-padded prompt, then
    the served tokens but the last); position ``first + i`` predicts
    ``served[i]``.  With ``quant`` set, the control's own first choice at
    each position is read instead of ``served``, against the float32 logits.
    Runs layer by layer, one sequence at a time, right-padded to ``pad_to``
    (causal: padding at the end changes no earlier position).
    """
    n = len(served)
    width = max(pad_to, len(seq))
    toks = np.zeros((1, width), np.int32)
    toks[0, : len(seq)] = seq
    x = _embed(params, jnp.asarray(toks), a=a)
    xq = x
    for i in range(a.layers):
        x = _layer_at(params["blocks"], jnp.asarray(i), x, a=a)
        if quant:
            xq = _layer_at(params["blocks"], jnp.asarray(i), xq, a=a, quant=quant)
    ref = _logits_rows(params, x, first, n, a, "")[0]
    best = jnp.max(ref, -1)
    if quant:
        pick = jnp.argmax(_logits_rows(params, xq, first, n, a, quant)[0], -1)
    else:
        pick = jnp.asarray(served, jnp.int32)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return np.asarray(best - got)


# ------------------------------------------------------------------ training
def row_loss_sum(params: Dict[str, Any], toks: jax.Array, labels: jax.Array, a: Arch,
                 quant: str = "") -> Tuple[jax.Array, jax.Array]:
    """Summed next-token cross-entropy of rows (B, S) and the count of labels."""
    x = params["embed"][toks].astype(jnp.float32)

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: layer(lp, x, a, quant))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    logits = head(params, x, a, quant)
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - ll, 0.0)), jnp.sum(valid)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def loss_and_grad(params: Dict[str, Any], toks: jax.Array, labels: jax.Array, a: Arch,
                  quant: str = "") -> Tuple[jax.Array, Any]:
    """Mean loss over a batch and its gradient, one row at a time."""
    def one(carry, row):
        t, l = row
        (s, c), g = jax.value_and_grad(lambda p: row_loss_sum(p, t[None], l[None], a, quant),
                                       has_aux=True)(params)
        gs, ss, cs = carry
        return (jax.tree.map(jnp.add, gs, g), ss + s, cs + c), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (g, s, c), _ = jax.lax.scan(one, (zero, 0.0, 0), (toks, labels))
    c = jnp.maximum(c, 1).astype(jnp.float32)
    return s / c, jax.tree.map(lambda t: t / c, g)


def global_norm(g: Any) -> float:
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(t)) for t in jax.tree.leaves(g))))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"), donate_argnums=(0, 2, 3))
def adamw_leaf(p, g, m, v, t, lr, scale, b1=0.9, b2=0.95, eps=1e-8, wd=0.1):
    """AdamW on one leaf, with bias correction and decoupled weight decay
    scaled by lr; ``scale`` is the global-norm clip factor of the gradient."""
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - lr * (step + wd * p), m, v


def leaf_norms(tree: Any, scale: float = 1.0) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): scale * float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for k, v in flat}


def train_readings(make_params0: Callable[[], Dict[str, Any]],
                   batches: List[Tuple[np.ndarray, np.ndarray]], a: Arch,
                   hyper: Dict[str, float], quant: str = "") -> Dict[str, Any]:
    """The reference's first ``len(batches)`` steps from ``make_params0()``.

    Returns each step's loss, each leaf's norm of the first clipped gradient,
    and each leaf's norm of the parameters' change over all the steps.  The
    Adam moments wait on the host between steps, and the weights are drawn
    again for the change, so that the float32 state fits one chip beside
    the gradient.
    """
    flat0, tdef = jax.tree.flatten(make_params0())
    p = [t.astype(jnp.float32) for t in flat0]
    del flat0
    m = [np.zeros(t.shape, np.float32) for t in p]
    v = [np.zeros(t.shape, np.float32) for t in p]
    losses, grad_norms = [], None
    for t, (toks, labels) in enumerate(batches, start=1):
        loss, g = loss_and_grad(jax.tree.unflatten(tdef, p), jnp.asarray(toks),
                                jnp.asarray(labels), a=a, quant=quant)
        norm = global_norm(g)
        scale = min(1.0, hyper["clip_norm"] / max(norm, 1e-9))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(g, scale)
        g = jax.tree.leaves(g)
        lr = lr_at(hyper, t - 1)
        for i in range(len(p)):
            p[i], mi, vi = adamw_leaf(p[i], g[i], jnp.asarray(m[i]), jnp.asarray(v[i]),
                                      float(t), lr, scale, wd=hyper["weight_decay"])
            g[i] = None
            m[i], v[i] = np.asarray(mi), np.asarray(vi)
        del g
    flat0 = jax.tree.leaves(make_params0())
    change = [p[i] - flat0[i].astype(jnp.float32) for i in range(len(p))]
    return {"loss": losses, "grad": grad_norms, "change": leaf_norms(jax.tree.unflatten(tdef, change))}


def lr_at(hyper: Dict[str, float], step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_frac`` of the peak."""
    base, warm, total = hyper["base_lr"], hyper["warmup"], hyper["total"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    mf = hyper.get("min_frac", 0.1)
    return base * (mf + (1 - mf) * 0.5 * (1 + math.cos(math.pi * prog)))
