"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the exact TPU kernel body on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


# ---------------------------------------------------------------------------
# lora_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,r", [
    (64, 64, 64, 4), (100, 96, 72, 8), (256, 128, 512, 16),
    (33, 70, 65, 2),  # awkward non-multiples exercise padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_sweep(m, k, n, r, dtype):
    kk = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kk[0], (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(kk[1], (k, n), jnp.float32).astype(dtype)
    a = jax.random.normal(kk[2], (k, r), jnp.float32).astype(dtype)
    b = jax.random.normal(kk[3], (r, n), jnp.float32).astype(dtype)
    got = ops.lora_matmul(x, w, a, b, 0.5, bm=32, bn=64, bk=32)
    want = ref.lora_matmul_ref(x, w, a, b, 0.5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype] * np.abs(np.asarray(want, np.float32)).max(),
        rtol=0)


def test_lora_matmul_batched_leading_dims():
    kk = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(kk[0], (2, 17, 64))
    w = jax.random.normal(kk[1], (64, 48))
    a = jax.random.normal(kk[2], (64, 4))
    b = jax.random.normal(kk[3], (4, 48))
    got = ops.lora_matmul(x, w, a, b, 1.0, bm=16, bn=16, bk=16)
    want = ref.lora_matmul_ref(x.reshape(-1, 64), w, a, b, 1.0).reshape(2, 17, 48)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("g,m,k,n,r,e", [
    (3, 1, 64, 48, 4, 2),     # decode shape: one token per request
    (4, 8, 128, 128, 8, 4),
    (2, 5, 100, 72, 4, 5),    # awkward non-multiples exercise padding
    (6, 1, 256, 96, 16, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_grouped_sweep(g, m, k, n, r, e, dtype):
    kk = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(kk[0], (g, m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(kk[1], (k, n), jnp.float32).astype(dtype)
    a = jax.random.normal(kk[2], (e, k, r), jnp.float32).astype(dtype)
    b = jax.random.normal(kk[3], (e, r, n), jnp.float32).astype(dtype)
    ids = jax.random.randint(kk[4], (g,), 0, e)
    got = ops.lora_matmul_grouped(x, w, a, b, ids, 0.5, bn=64, bk=32)
    want = ref.lora_matmul_grouped_ref(x, w, a, b, ids, 0.5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype] * np.abs(np.asarray(want, np.float32)).max(),
        rtol=0)


def test_lora_matmul_grouped_matches_single_adapter_loop():
    """The multi-tenant kernel must equal a per-request lora_matmul loop."""
    kk = jax.random.split(jax.random.PRNGKey(3), 5)
    g, m, k, n, r, e = 5, 4, 96, 80, 8, 3
    x = jax.random.normal(kk[0], (g, m, k))
    w = jax.random.normal(kk[1], (k, n))
    a = jax.random.normal(kk[2], (e, k, r))
    b = jax.random.normal(kk[3], (e, r, n))
    ids = jax.random.randint(kk[4], (g,), 0, e)
    got = ops.lora_matmul_grouped(x, w, a, b, ids, 0.7, bn=32, bk=32)
    want = jnp.stack([ops.lora_matmul(x[gi], w, a[aid], b[aid], 0.7,
                                      bm=16, bn=32, bk=32)
                      for gi, aid in enumerate(np.asarray(ids))])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_lora_matmul_grouped_2d_rows():
    """(G, K) input (one token per request, no M axis) squeezes through."""
    kk = jax.random.split(jax.random.PRNGKey(4), 5)
    g, k, n, r, e = 4, 64, 48, 4, 2
    x = jax.random.normal(kk[0], (g, k))
    w = jax.random.normal(kk[1], (k, n))
    a = jax.random.normal(kk[2], (e, k, r))
    b = jax.random.normal(kk[3], (e, r, n))
    ids = jnp.asarray([0, 1, 1, 0])
    got = ops.lora_matmul_grouped(x, w, a, b, ids, 1.0, bn=16, bk=16)
    want = ref.lora_matmul_grouped_ref(x[:, None, :], w, a, b, ids, 1.0)[:, 0]
    assert got.shape == (g, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,skv,hq,hkv,d", [
    (128, 128, 4, 4, 32),    # MHA
    (128, 128, 8, 2, 32),    # GQA
    (200, 200, 4, 2, 64),    # non-multiple seq
    (96, 96, 25, 5, 16),     # hymba-style head count
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_sweep(sq, skv, hq, hkv, d, window):
    kk = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kk[0], (2, sq, hq, d))
    k = jax.random.normal(kk[1], (2, skv, hkv, d))
    v = jax.random.normal(kk[2], (2, skv, hkv, d))
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    from repro.models.attention import naive_attention
    pos = jnp.broadcast_to(jnp.arange(sq), (2, sq))
    want = naive_attention(q, k, v, causal=True, window=window,
                           q_positions=pos, k_positions=pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    kk = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kk[0], (1, 128, 4, 32)).astype(dtype)
    k = jax.random.normal(kk[1], (1, 128, 4, 32)).astype(dtype)
    v = jax.random.normal(kk[2], (1, 128, 4, 32)).astype(dtype)
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention_ref(
        q.reshape(4, 128, 32).transpose(0, 1, 2),
        k.reshape(4, 128, 32), v.reshape(4, 128, 32))
    # reshape mismatch: use the model-side oracle instead
    from repro.models.attention import naive_attention
    pos = jnp.broadcast_to(jnp.arange(128), (1, 128))
    want = naive_attention(q, k, v, causal=True, window=0,
                           q_positions=pos, k_positions=pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


@pytest.mark.parametrize("window,s,block", [
    (0, 200, None),       # one 256-row major block of 2 x 2 minor blocks
    (24, 72, 32),         # three 32-row major blocks, each its own minor
    (0, 600, 256),        # three 256-row major blocks of 2 x 2 minor blocks
    (130, 600, 256),      # the same; the window skips whole major blocks
], ids=["causal", "window24", "causal_major256", "window130_major256"])
@pytest.mark.parametrize("scale", [None, 1 / 64], ids=["rsqrt_d", "1_64"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_flash_attention_grad(group, d, scale, window, s, block):
    """Forward and ``jax.grad`` in q, k and v against naive_attention, KV
    heads read through the index maps. The sequence is never a multiple of
    the block, and the major blocks are one (static) or several (traced)."""
    blocks = {"block_q": block, "block_k": block} if block else {}
    hkv = 2
    kk = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(kk[0], (1, s, group * hkv, d))
    k = jax.random.normal(kk[1], (1, s, hkv, d))
    v = jax.random.normal(kk[2], (1, s, hkv, d))
    ct = jax.random.normal(kk[3], q.shape)
    pos = jnp.broadcast_to(jnp.arange(s), (1, s))
    from repro.models.attention import naive_attention

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, window=window,
                                   scale=scale, **blocks)

    def naive(q, k, v):
        return naive_attention(q, k, v, causal=True, window=window,
                               q_positions=pos, k_positions=pos, scale=scale)

    @functools.partial(jax.jit, static_argnums=0)
    def out_and_grads(fn, q, k, v):
        out, pullback = jax.vjp(fn, q, k, v)
        return (out, *pullback(ct))

    for got, want in zip(out_and_grads(flash, q, k, v),
                         out_and_grads(naive, q, k, v), strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("l,nh,hp,ns,chunk", [
    (64, 2, 16, 16, 16), (100, 3, 16, 24, 32), (256, 4, 32, 64, 64),
])
def test_ssd_scan_sweep(l, nh, hp, ns, chunk):
    kk = jax.random.split(jax.random.PRNGKey(4), 4)
    xt = jax.random.normal(kk[0], (2, l, nh, hp)) * 0.2
    a = -jnp.abs(jax.random.normal(kk[1], (2, l, nh))) * 0.1
    B = jax.random.normal(kk[2], (2, l, ns)) * 0.3
    C = jax.random.normal(kk[3], (2, l, ns)) * 0.3
    y1, h1 = ops.ssd_scan(xt, a, B, C, chunk)
    from repro.models.mamba import ssd_chunked
    y2, h2 = ssd_chunked(xt, a, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


def test_ssd_intra_chunk_against_ref():
    from repro.kernels.ssd_scan import ssd_intra_chunk
    kk = jax.random.split(jax.random.PRNGKey(5), 4)
    b, nc, cl, nh, hp, ns = 2, 3, 16, 2, 8, 12
    xt = jax.random.normal(kk[0], (b, nc, cl, nh, hp)) * 0.2
    a = -jnp.abs(jax.random.normal(kk[1], (b, nc, cl, nh))) * 0.1
    B = jax.random.normal(kk[2], (b, nc, cl, ns)) * 0.3
    C = jax.random.normal(kk[3], (b, nc, cl, ns)) * 0.3
    y1, st1, dec1 = ssd_intra_chunk(xt, a, B, C, interpret=True)
    y2, st2, dec2 = ref.ssd_intra_chunk_ref(xt, a, B, C)
    # kernel emits states as (ns, hp); ref as (nh, ns, hp) per chunk
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    st1t = np.asarray(st1)                       # (b, nc, nh, ns, hp)
    st2t = np.asarray(st2)                       # (b, nc, nh, ns, hp)
    np.testing.assert_allclose(st1t, st2t, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dec1[..., 0]),
                               np.asarray(dec2[..., 0]), atol=1e-4)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,hq,hkv,d,t,window", [
    (100, 8, 4, 32, 0, 0),      # first token
    (100, 8, 4, 32, 42, 0),     # mid-cache
    (100, 8, 4, 32, 99, 0),     # full cache
    (100, 8, 4, 32, 60, 32),    # sliding window over linear cache
    (64, 4, 4, 16, 150, 64),    # ring buffer (window == slots, t > slots)
])
def test_flash_decode_sweep(s, hq, hkv, d, t, window):
    from repro.models.attention import naive_attention
    kk = jax.random.split(jax.random.PRNGKey(7), 3)
    b = 2
    q = jax.random.normal(kk[0], (b, 1, hq, d))
    k = jax.random.normal(kk[1], (b, s, hkv, d))
    v = jax.random.normal(kk[2], (b, s, hkv, d))
    got = ops.flash_decode(q, k, v, jnp.int32(t), window=window, block_k=32)
    pos = jnp.full((b, 1), t, jnp.int32)
    j = jnp.arange(s, dtype=jnp.int32)
    if window and window <= s and t >= s:
        abs_pos = t - ((t - j) % s)
        abs_pos = jnp.where(abs_pos >= 0, abs_pos, 2**30)
        kpos = jnp.broadcast_to(abs_pos, (b, s))
    else:
        kpos = jnp.broadcast_to(j, (b, s))
    want = naive_attention(q, k, v, causal=True, window=window,
                           q_positions=pos, k_positions=kpos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_decode_bf16(dtype):
    kk = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(kk[0], (1, 1, 4, 32)).astype(dtype)
    k = jax.random.normal(kk[1], (1, 96, 2, 32)).astype(dtype)
    v = jax.random.normal(kk[2], (1, 96, 2, 32)).astype(dtype)
    got = ops.flash_decode(q, k, v, jnp.int32(95), block_k=32)
    from repro.models.attention import naive_attention
    pos = jnp.full((1, 1), 95, jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(96), (1, 96))
    want = naive_attention(q, k, v, causal=True, window=0,
                           q_positions=pos, k_positions=kpos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
