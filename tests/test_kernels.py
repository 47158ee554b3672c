"""Per-kernel correctness: Pallas (interpret mode) and jnp variants vs oracles.

Two layers: the original spot-checks (hand-picked shapes per code path) and
a seeded dtype × shape parity GRID per kernel — every tunable implementation
against its ``ref.py`` oracle across bucket-boundary and non-power-of-two
edge shapes, with tolerances *derived* from the dtype's input precision
rather than hand-tuned per test.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention import ref as attn_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref
from repro.kernels.ssd.kernel import ssd_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def _grid_tol(dtype, headroom: float = 1.0):
    """Tolerance derived from the dtype's unit roundoff.  The error models
    differ: in f32 the rounding happens *inside* the reduction chain, so eps
    (2⁻²³) is amplified by the softmax/scan length (factor ≈170 covers these
    sizes); in bf16 only the INPUTS are rounded (eps 2⁻⁸) while accumulation
    stays f32, so the amplification is O(1) (factor 5 ≈ the hand-tuned 2e-2
    of the spot checks)."""
    if dtype == jnp.bfloat16:
        t = 5.0 * 2.0 ** -8 * headroom
    else:
        t = 170.0 * float(np.finfo(np.float32).eps) * headroom
    return dict(rtol=t, atol=t)


def _seeded_key(*parts) -> jax.Array:
    # zlib.crc32, not hash(): string hashing is salted per interpreter, and
    # the grid must draw the same data on every run (deflake rule).
    return jax.random.PRNGKey(zlib.crc32("/".join(map(str, parts)).encode()) % (1 << 31))


def _mk_qkv(key, b, sq, sk, h, k, d, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32).astype(dtype)
    kk_ = jax.random.normal(kk, (b, sk, k, d), jnp.float32).astype(dtype)
    vv = jax.random.normal(kv, (b, sk, k, d), jnp.float32).astype(dtype)
    return q, kk_, vv


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 4, 2, 32), (2, 128, 4, 4, 64)])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_pallas_vs_naive(dtype, shape, window):
    b, s, h, k, d = shape
    q, kk, vv = _mk_qkv(jax.random.PRNGKey(0), b, s, s, h, k, d, dtype)
    want = attn_ref.naive_attention(q, kk, vv, causal=True, window=window)
    got = flash_attention_pallas(q, kk, vv, causal=True, window=window,
                                 block_q=64, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("impl", ["scan", "unrolled"])
@pytest.mark.parametrize("window", [0, 32])
@pytest.mark.parametrize("q_offset", [0, 64])
def test_jnp_impls_vs_naive(impl, window, q_offset):
    b, h, k, d = 2, 4, 2, 16
    sk = 128
    sq = sk - q_offset
    q, kk, vv = _mk_qkv(jax.random.PRNGKey(1), b, sq, sk, h, k, d, jnp.float32)
    want = attn_ref.naive_attention(q, kk, vv, causal=True, window=window, q_offset=q_offset)
    fn = attn_ref.scan_attention if impl == "scan" else attn_ref.unrolled_attention
    got = fn(q, kk, vv, causal=True, window=window, q_offset=q_offset, block_kv=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_pallas_qoffset():
    b, h, k, d, sk = 1, 2, 2, 32, 128
    q_offset = 64
    q, kk, vv = _mk_qkv(jax.random.PRNGKey(2), b, sk - q_offset, sk, h, k, d, jnp.float32)
    want = attn_ref.naive_attention(q, kk, vv, causal=True, q_offset=q_offset)
    got = flash_attention_pallas(q, kk, vv, causal=True, q_offset=q_offset,
                                 block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_incremental_naive():
    b, h, k, d, c = 2, 4, 2, 16, 32
    key = jax.random.PRNGKey(3)
    q, kk, vv = _mk_qkv(key, b, c, c, h, k, d, jnp.float32)
    # full naive on c tokens; compare the last token vs decode_attention
    want = attn_ref.naive_attention(q, kk, vv, causal=True)[:, -1:]
    got = attn_ref.decode_attention(q[:, -1:], kk, vv, jnp.asarray(c - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_attention_ring_buffer():
    """Windowed ring cache must equal full-cache windowed attention."""
    b, h, k, d, w = 1, 2, 2, 16, 16
    total = 40  # tokens seen so far; pos = total - 1
    key = jax.random.PRNGKey(4)
    q, kk, vv = _mk_qkv(key, b, total, total, h, k, d, jnp.float32)
    want = attn_ref.naive_attention(q, kk, vv, causal=True, window=w)[:, -1:]
    # build the ring cache: token t at slot t % w, last w tokens
    slots = [(total - w + i) for i in range(w)]
    ring_k = np.zeros((b, w, k, d), np.float32)
    ring_v = np.zeros((b, w, k, d), np.float32)
    for t in slots:
        ring_k[:, t % w] = np.asarray(kk[:, t])
        ring_v[:, t % w] = np.asarray(vv[:, t])
    got = attn_ref.decode_attention(q[:, -1:], jnp.asarray(ring_k), jnp.asarray(ring_v),
                                    jnp.asarray(total - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [0, 16])
def test_decode_attention_head_major_matches_sequence_major(window):
    """Grouped heads over a head-major (B, K, C, D) cache read what the
    sequence-major (B, C, K, D) cache gives, ring included, per-row pos."""
    b, h, k, d, c = 3, 6, 2, 16, 16
    q, kk, vv = _mk_qkv(jax.random.PRNGKey(5), b, 1, c, h, k, d, jnp.float32)
    # a ring (window == C) may run past its end; a full cache may not
    pos = jnp.asarray([3, 15, 37 if window == c else 9], jnp.int32)
    want = attn_ref.decode_attention(q, kk, vv, pos)
    got = attn_ref.decode_attention(q, jnp.swapaxes(kk, 1, 2), jnp.swapaxes(vv, 1, 2), pos,
                                    kv_head_major=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_decode_attention_negative_pos_is_an_empty_row():
    """A negative ``pos`` marks an empty row: zeros, and the other rows
    exactly what they read without it."""
    b, h, k, d, c = 3, 4, 2, 16, 16
    q, kk, vv = _mk_qkv(jax.random.PRNGKey(6), b, 1, c, h, k, d, jnp.float32)
    live = attn_ref.decode_attention(q, kk, vv, jnp.asarray([5, 5, 20], jnp.int32))
    got = attn_ref.decode_attention(q, kk, vv, jnp.asarray([-1, 5, 20], jnp.int32))
    assert not np.asarray(got[0]).any()
    np.testing.assert_array_equal(np.asarray(got[1:]), np.asarray(live[1:]))


# (C, K, D, head-major) -> tokens a block: olmo-1b, StarCoder2's ring, an
# odd capacity; 0 where the TPU's tiling refuses the copy (D of 64 as in
# hymba and seamless, an odd C head-major, K not a multiple of 8
# sequence-major), and the reference runs
DECODE_BLOCKS = [
    ((2048, 16, 128, False), 256),
    ((4096, 4, 128, True), 1024),
    ((100, 16, 128, False), 4),
    ((2048, 5, 64, True), 0),
    ((512, 16, 64, False), 0),
    ((100, 8, 128, True), 0),
    ((256, 12, 128, False), 0),
]


@pytest.mark.parametrize("shape,blk", DECODE_BLOCKS)
def test_decode_block_from_the_cache_shape(shape, blk):
    from repro.kernels.flash_attention.kernel import decode_block

    assert decode_block(*shape) == blk


# (kv_head_major, K, H, D): both cache orders, one query per KV head and
# grouped; C is two blocks of ``decode_block`` at each shape
DECODE_KERNEL = {
    "seq_mha": (False, 16, 16, 128),
    "seq_grouped": (False, 8, 16, 128),
    "head_mha": (True, 8, 8, 128),
    "head_grouped": (True, 4, 12, 128),
}


@pytest.mark.parametrize("case", list(DECODE_KERNEL))
def test_decode_attention_pallas_vs_ref(case):
    """The decode kernel (interpret mode) against ``ref.decode_attention`` on
    layer 1 of a two-layer stack, in the configured precision (bf16 cache,
    f32 scores).  Rows: empty, shorter than a block, ending on a block edge,
    one token into the second block, full, and a ring wrapped past C."""
    from repro.kernels.flash_attention.kernel import decode_attention_pallas, decode_block

    head_major, n_kv, h, d = DECODE_KERNEL[case]
    blk = decode_block(1 << 30, n_kv, d, head_major)
    c, n_layers, layer = 2 * blk, 2, 1
    pos = jnp.asarray([-1, 40, blk - 1, blk, c - 1, c + 300], jnp.int32)
    b = pos.shape[0]
    kq, kk, kv = jax.random.split(_seeded_key("decode_pallas", case), 3)
    shape = (n_layers, b, n_kv, c, d) if head_major else (n_layers, b, c, n_kv, d)
    ks = jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
    vs = jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16)
    q = jax.random.normal(kq, (b, 1, h, d), jnp.float32).astype(jnp.bfloat16)
    assert decode_block(c, n_kv, d, head_major) == blk
    lengths = jnp.where(pos < 0, 0, jnp.minimum(pos + 1, c))
    got = decode_attention_pallas(q, ks, vs, lengths, jnp.int32(layer),
                                  kv_head_major=head_major, interpret=True)
    want = attn_ref.decode_attention(q, ks[layer], vs[layer], pos, kv_head_major=head_major)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert not np.asarray(got[0]).any()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, **_tol(jnp.bfloat16))
    # no less precise than the reference: both against float32 throughout
    f32 = [t.astype(jnp.float32) for t in (q, ks[layer], vs[layer])]
    exact = np.asarray(attn_ref.decode_attention(*f32, pos, kv_head_major=head_major))
    assert np.abs(got - exact).max() <= 1.5 * np.abs(want - exact).max()


# ------------------------------------------------------- attention parity grid
# (b, s, h, k, d): bucket-boundary and non-pow2 edge shapes the spot checks
# above never touch — s=96/72/33 exercise the ops' block-alignment fallback.
ATTN_GRID = [
    (1, 96, 2, 1, 32),
    (2, 72, 4, 2, 16),
    (1, 160, 4, 4, 64),
    (1, 33, 2, 1, 16),
    (2, 256, 2, 2, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", ATTN_GRID)
@pytest.mark.parametrize("impl", ["scan", "unrolled", "unrolled_full"])
def test_flash_impl_parity_grid(dtype, shape, impl):
    b, s, h, k, d = shape
    q, kk, vv = _mk_qkv(_seeded_key("attn", shape, dtype, impl), b, s, s, h, k, d, dtype)
    want = attn_ref.naive_attention(q, kk, vv, causal=True)
    got = attn_ops.flash_attention(q, kk, vv, causal=True, impl=impl,
                                   block_q=64, block_kv=32)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **_grid_tol(dtype))


@pytest.mark.parametrize("shape", [(1, 96, 2, 1, 32), (1, 72, 2, 2, 16)])
def test_flash_pallas_parity_grid_nonpow2(shape):
    """Pallas (interpret) on non-pow2 seqs: block sizes align by halving."""
    b, s, h, k, d = shape
    q, kk, vv = _mk_qkv(_seeded_key("attn_pallas", shape), b, s, s, h, k, d, jnp.float32)
    want = attn_ref.naive_attention(q, kk, vv, causal=True)
    got = flash_attention_pallas(q, kk, vv, causal=True, block_q=24 if s == 72 else 32,
                                 block_kv=24 if s == 72 else 32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_grid_tol(jnp.float32))


# --------------------------------------------------------------------- ssd
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 128, 4, 16, 8, 1), (1, 128, 4, 32, 16, 2)])
def test_ssd_chunked_vs_naive(dtype, shape):
    b, s, h, p, n, g = shape
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (b, s, g, n), jnp.float32).astype(dtype)
    D = jnp.ones((h,), jnp.float32)
    want, wstate = ssd_ref.ssd_naive_scan(x, dt, A, B, C, D, return_state=True)
    got, gstate = ssd_ref.ssd_chunked(x, dt, A, B, C, D, chunk=32, return_state=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(gstate), np.asarray(wstate), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("chunk", [32, 64])
def test_ssd_pallas_vs_naive(chunk):
    b, s, h, p, n, g = 1, 128, 2, 16, 8, 1
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
    D = jnp.ones((h,), jnp.float32)
    want = ssd_ref.ssd_naive_scan(x, dt, A, B, C, D)
    got = ssd_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ ssd parity grid
# (b, s, h, p, n, g) incl. non-pow2 seqs (s=96/72: the op halves the chunk
# until it divides) and a state-dim the spot checks skip.
SSD_GRID = [
    (1, 96, 2, 8, 4, 1),
    (2, 72, 4, 16, 8, 2),
    (1, 256, 2, 16, 8, 1),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SSD_GRID)
@pytest.mark.parametrize("impl", ["chunked", "chunked_unrolled"])
def test_ssd_impl_parity_grid(dtype, shape, impl):
    b, s, h, p, n, g = shape
    ks = jax.random.split(_seeded_key("ssd", shape, dtype, impl), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (b, s, g, n), jnp.float32).astype(dtype)
    D = jnp.ones((h,), jnp.float32)
    want = ssd_ref.ssd_naive_scan(x, dt, A, B, C, D)
    got = ssd_ops.ssd(x, dt, A, B, C, D, impl=impl, chunk=32)
    # The inter-chunk recurrence accumulates over s/chunk state hand-offs:
    # give the derived tolerance that extra headroom.
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **_grid_tol(dtype, headroom=4.0))


def test_ssd_decode_matches_scan():
    b, s, h, p, n, g = 2, 16, 2, 8, 4, 1
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
    want, _ = ssd_ref.ssd_naive_scan(x, dt, A, B, C, None, return_state=True)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    outs = []
    for t in range(s):
        y, state = ssd_ref.ssd_decode_step(state, x[:, t], dt[:, t], A, B[:, t], C[:, t], None)
        outs.append(y)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (2, 16, 256)])
@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_pallas(dtype, shape, residual):
    key = jax.random.PRNGKey(8)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    r = jax.random.normal(k2, shape, jnp.float32).astype(dtype) if residual else None
    scale = jnp.linspace(0.5, 1.5, shape[-1], dtype=jnp.float32)
    want = rms_ref.rmsnorm(x, scale, r)
    got = rmsnorm_pallas(x, scale, r, block_rows=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


# -------------------------------------------------------- rmsnorm parity grid
# Non-pow2 rows force block_rows down to odd divisors (3 rows → block 1);
# non-pow2 feature dims exercise the reduction width.
RMS_GRID = [
    (3, 96),
    (6, 160),
    (2, 5, 48),
    (7, 1024),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", RMS_GRID)
@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_parity_grid(dtype, shape, residual):
    k1, k2 = jax.random.split(_seeded_key("rms", shape, dtype, residual))
    x = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    r = jax.random.normal(k2, shape, jnp.float32).astype(dtype) if residual else None
    scale = jnp.linspace(0.5, 1.5, shape[-1], dtype=jnp.float32)
    want = rms_ref.rmsnorm(x, scale, r)
    got = rmsnorm_pallas(x, scale, r, block_rows=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **_grid_tol(dtype))
