"""GQA attention: init, training/prefill forward, cached decode.

Three score paths:
  * ``naive``   — full (Sq, Skv) score matrix; oracle for tests.
  * ``chunked`` — flash-style online softmax in pure jnp (lax.scan over KV
                  blocks, lax.map over Q blocks). O(S·block) memory; this is
                  the path the multi-pod dry-run lowers.
  * ``pallas``  — the TPU Pallas kernels in ``repro.kernels.flash_attention``,
                  forward and backward, for positions 0..S-1 (validated in
                  interpret mode on CPU); the split step's path on a TPU.

Supports causal masking, sliding windows (SWA), GQA head grouping, RoPE or
no positions (NoPE, Granite 4.0-H), a published softmax scale in place of
1/sqrt(head_dim), qk-norm (Qwen3) and QKV bias (Qwen2).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (ACC_DTYPE, Params, apply_rope, dense_init,
                                 init_lora_pair, init_rms_norm, lora_dense,
                                 maybe_lora, rms_norm)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, q_dim, dtype),
        "wk": dense_init(ks[1], d, kv_dim, dtype),
        "wv": dense_init(ks[2], d, kv_dim, dtype),
        "wo": dense_init(ks[3], q_dim, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((q_dim,), dtype)
        p["bk"] = jnp.zeros((kv_dim,), dtype)
        p["bv"] = jnp.zeros((kv_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(cfg.resolved_head_dim)
        p["k_norm"] = init_rms_norm(cfg.resolved_head_dim)
    return p


def init_attention_lora(key, cfg: ModelConfig) -> Params:
    r = cfg.lora.rank
    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    out: Params = {}
    t = cfg.lora.targets
    ldt = jnp.dtype(cfg.lora.dtype)
    if "wq" in t:
        out["wq"] = init_lora_pair(ks[0], d, q_dim, r, ldt)
    if "wk" in t:
        out["wk"] = init_lora_pair(ks[1], d, kv_dim, r, ldt)
    if "wv" in t:
        out["wv"] = init_lora_pair(ks[2], d, kv_dim, r, ldt)
    if "wo" in t:
        out["wo"] = init_lora_pair(ks[3], q_dim, d, r, ldt)
    return out


# ---------------------------------------------------------------------------
# Score paths
# ---------------------------------------------------------------------------


def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def naive_attention(q, k, v, *, causal: bool, window: int,
                    q_positions, k_positions,
                    scale: Optional[float] = None) -> jax.Array:
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D). Oracle path. ``scale``: the
    softmax scale, 1/sqrt(D) if None."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(ACC_DTYPE),
                        k.astype(ACC_DTYPE))
    scores = (scores / jnp.sqrt(float(d)) if scale is None
              else scores * scale)
    mask = k_positions[:, None, :] <= q_positions[:, :, None]  # (B,Sq,Skv)
    if not causal:
        mask = jnp.ones_like(mask)
    if window:
        mask &= k_positions[:, None, :] > (q_positions[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(ACC_DTYPE))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: int,
                      q_positions, k_positions, scale: Optional[float] = None,
                      block_q: int = 512, block_k: int = 1024) -> jax.Array:
    """Flash-style online softmax, pure jnp. Same signature as naive."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    if sq <= block_q and skv <= block_k:
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_positions=q_positions, k_positions=k_positions,
                               scale=scale)

    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, ((0, 0), (0, pad_q)), constant_values=-1)
    kpos = jnp.pad(k_positions, ((0, 0), (0, pad_k)),
                   constant_values=2**30)  # padded keys masked out everywhere
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k

    # flash-style mixed precision: q/k/v stay in their storage dtype (bf16
    # in production) — only the per-block scores and the running (acc, m, l)
    # statistics live in f32. Halves attention HBM traffic vs upcasting.
    qb = qp.reshape(b, nq, block_q, hkv, group, d)
    kb = kp.reshape(b, nk, block_k, hkv, d)
    vb = vp.reshape(b, nk, block_k, hkv, d)
    qposb = qpos.reshape(b, nq, block_q)
    kposb = kpos.reshape(b, nk, block_k)
    if scale is None:
        scale = 1.0 / jnp.sqrt(float(d))

    def one_q_block(args):
        qi, qpos_i = args  # (b, block_q, hkv, g, d), (b, block_q)

        def kv_step(carry, kv):
            acc, m, l = carry
            ki, vi, kpos_i = kv
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, ki,
                           preferred_element_type=ACC_DTYPE) * scale
            mask = kpos_i[:, None, :] <= qpos_i[:, :, None]
            if not causal:
                mask = kpos_i[:, None, :] < 2**30
            if window:
                mask &= kpos_i[:, None, :] > (qpos_i[:, :, None] - window)
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vi.dtype), vi,
                preferred_element_type=ACC_DTYPE)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, hkv, group, block_q, d), ACC_DTYPE)
        m0 = jnp.full((b, hkv, group, block_q), NEG_INF, ACC_DTYPE)
        l0 = jnp.zeros((b, hkv, group, block_q), ACC_DTYPE)
        (acc, _, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kposb.transpose(1, 0, 2)))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return out.transpose(0, 3, 1, 2, 4)  # (b, block_q, hkv, g, d)

    out = jax.lax.map(one_q_block,
                      (qb.transpose(1, 0, 2, 3, 4, 5),
                       qposb.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * block_q, hq, d)
    return out[:, :sq].astype(q.dtype)


def softmax_scale(cfg: ModelConfig) -> Optional[float]:
    """The published softmax scale, or None for 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or None


def positioned(x: jax.Array, positions: jax.Array, cfg: ModelConfig
               ) -> jax.Array:
    """RoPE at ``positions``; NoPE configurations take none."""
    if cfg.position_embedding == "nope":
        return x
    return apply_rope(x, positions, cfg.rope_theta)


def attention_scores(q, k, v, *, impl: str, causal: bool, window: int,
                     q_positions, k_positions,
                     scale: Optional[float] = None) -> jax.Array:
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_positions=q_positions, k_positions=k_positions,
                               scale=scale)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_positions=q_positions, k_positions=k_positions,
                                 scale=scale)
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops
        return kernel_ops.flash_attention(q, k, v, causal=causal, window=window,
                                          scale=scale, q_positions=q_positions,
                                          k_positions=k_positions)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------------------
# Forward (train / prefill) and cached decode
# ---------------------------------------------------------------------------


def attention_forward(params: Params, lora: Optional[Params], x: jax.Array,
                      cfg: ModelConfig, *, positions: jax.Array,
                      impl: str = "chunked",
                      use_lora_kernel: bool = False
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention. Returns (out, {"k","v"} post-RoPE for cache)."""
    scale = cfg.lora.scale
    q = lora_dense(x, params["wq"], maybe_lora(lora, "wq"), scale,
                   params.get("bq"), use_kernel=use_lora_kernel)
    k = lora_dense(x, params["wk"], maybe_lora(lora, "wk"), scale,
                   params.get("bk"), use_kernel=use_lora_kernel)
    v = lora_dense(x, params["wv"], maybe_lora(lora, "wv"), scale,
                   params.get("bv"), use_kernel=use_lora_kernel)
    from repro.shardctx import constrain
    q = _split_heads(q, cfg.n_heads)
    k = _split_heads(k, cfg.n_kv_heads)
    v = _split_heads(v, cfg.n_kv_heads)
    # pin q-head sharding across the reshape boundary. (Tried and reverted:
    # forcing kv replication removed the per-block all-to-alls but cost +21%
    # total collective bytes — GSPMD's a2a plan was cheaper; §Perf-2 it.3.)
    q = constrain(q, "dp", None, "model", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    q = positioned(q, positions, cfg)
    k = positioned(k, positions, cfg)
    out = attention_scores(q, k, v, impl=impl, causal=True,
                           window=cfg.sliding_window,
                           q_positions=positions, k_positions=positions,
                           scale=softmax_scale(cfg))
    out = out.reshape(x.shape[0], x.shape[1], cfg.q_dim)
    out = lora_dense(out, params["wo"], maybe_lora(lora, "wo"), scale,
                     use_kernel=use_lora_kernel)
    return out, {"k": k, "v": v}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
                  ) -> Dict[str, jax.Array]:
    """Per-layer cache. SWA archs keep a ring buffer of ``window`` slots.

    ``cfg.kv_cache_dtype == 'int8'``: k/v stored int8 with one f32 scale per
    (slot, kv-head) — halves the resident decode footprint vs bf16."""
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, slots, cfg.n_kv_heads, cfg.resolved_head_dim)
    if cfg.kv_cache_dtype == "int8":
        sshape = (batch, slots, cfg.n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quant_kv(x: jax.Array):
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def _cache_abs_positions(t: jax.Array, slots: int, window: int, b: int
                         ) -> jax.Array:
    """(B, slots) absolute position held by each cache slot after the write
    at position(s) ``t`` (scalar or per-row (B,) vector).

    Linear cache: slot j holds position j (stale j > t masked causally).
    Ring (SWA):   slot j holds ``t - ((t - j) mod W)`` — valid iff >= 0.
    """
    j = jnp.arange(slots, dtype=jnp.int32)
    if window and window <= slots:
        tb = t[:, None] if t.ndim else jnp.broadcast_to(t, (b,))[:, None]
        abs_pos = tb - ((tb - j[None, :]) % slots)
        return jnp.where(abs_pos >= 0, abs_pos, 2**30)    # unwritten slots
    return jnp.broadcast_to(j, (b, slots))


def _write_kv(cache: Dict[str, jax.Array], k: jax.Array, v: jax.Array,
              slot: jax.Array, dtype, int8: bool
              ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Write one token per row at ``slot`` (scalar or (B,) vector).

    Returns (new_cache, dequantized k view, dequantized v view)."""
    new_cache: Dict[str, jax.Array] = {}
    if int8:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        entries = (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs))
    else:
        entries = (("k", k), ("v", v))
    for name, val in entries:
        if slot.ndim:                                      # per-row slots
            rows = jnp.arange(val.shape[0])
            new_cache[name] = cache[name].at[rows, slot].set(val[:, 0])
        else:
            new_cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], val, slot, axis=1)
    if int8:
        k_cache = (new_cache["k"].astype(jnp.float32)
                   * new_cache["k_scale"]).astype(dtype)
        v_cache = (new_cache["v"].astype(jnp.float32)
                   * new_cache["v_scale"]).astype(dtype)
    else:
        k_cache, v_cache = new_cache["k"], new_cache["v"]
    return new_cache, k_cache, v_cache


def attention_decode(params: Params, lora: Optional[Params], x: jax.Array,
                     cache: Dict[str, jax.Array], cfg: ModelConfig, *,
                     t: jax.Array, use_lora_kernel: bool = False
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B,1,d); t: int32 absolute position — a scalar
    (whole batch at one position) or a (B,) vector (continuous-batching
    serving: every row at its own position).

    Full cache: write at slot ``t``, attend over slots ``<= t``.
    Ring (SWA): write at ``t % W``; slot j holds absolute position
    ``t - ((t - j) mod W)`` — valid iff >= 0.
    """
    scale = cfg.lora.scale
    b = x.shape[0]
    t = jnp.asarray(t, jnp.int32)
    pos = t[:, None] if t.ndim else jnp.full((b, 1), t, jnp.int32)
    uk = use_lora_kernel
    q = lora_dense(x, params["wq"], maybe_lora(lora, "wq"), scale,
                   params.get("bq"), uk)
    k = lora_dense(x, params["wk"], maybe_lora(lora, "wk"), scale,
                   params.get("bk"), uk)
    v = lora_dense(x, params["wv"], maybe_lora(lora, "wv"), scale,
                   params.get("bv"), uk)
    q = _split_heads(q, cfg.n_heads)
    k = _split_heads(k, cfg.n_kv_heads)
    v = _split_heads(v, cfg.n_kv_heads)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    q = positioned(q, pos, cfg)
    k = positioned(k, pos, cfg)

    slots = cache["k"].shape[1]
    slot = (t % slots).astype(jnp.int32)
    new_cache, k_cache, v_cache = _write_kv(
        cache, k, v, slot, x.dtype, cfg.kv_cache_dtype == "int8")
    k_positions = _cache_abs_positions(t, slots, cfg.sliding_window, b)

    out = naive_attention(q, k_cache, v_cache, causal=True,
                          window=cfg.sliding_window,
                          q_positions=pos, k_positions=k_positions,
                          scale=softmax_scale(cfg))
    out = out.reshape(b, 1, cfg.q_dim)
    out = lora_dense(out, params["wo"], maybe_lora(lora, "wo"), scale,
                     None, uk)
    return out, new_cache


def attention_prefill(params: Params, lora: Optional[Params], x: jax.Array,
                      cache: Dict[str, jax.Array], cfg: ModelConfig, *,
                      positions: jax.Array, use_lora_kernel: bool = False
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cached multi-token prefill: one parallel pass over a prompt chunk.

    x: (B, C, d) chunk hidden states; ``positions``: (C,) absolute positions
    shared across the batch (chunks are fed in order, so the chunk occupies
    a contiguous position range). Writes the chunk's K/V into the cache
    (linear slot ``p``; ring slot ``p mod W`` — requires C <= slots so one
    chunk never overwrites itself) and attends over the WHOLE cache with
    the same masking semantics as ``attention_decode``, which is what makes
    chunk i see chunks < i. Returns (out (B, C, q_dim), new cache).
    """
    scale = cfg.lora.scale
    b, c, _ = x.shape
    pos = jnp.broadcast_to(positions[None, :], (b, c)).astype(jnp.int32)
    uk = use_lora_kernel
    q = lora_dense(x, params["wq"], maybe_lora(lora, "wq"), scale,
                   params.get("bq"), uk)
    k = lora_dense(x, params["wk"], maybe_lora(lora, "wk"), scale,
                   params.get("bk"), uk)
    v = lora_dense(x, params["wv"], maybe_lora(lora, "wv"), scale,
                   params.get("bv"), uk)
    q = _split_heads(q, cfg.n_heads)
    k = _split_heads(k, cfg.n_kv_heads)
    v = _split_heads(v, cfg.n_kv_heads)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    q = positioned(q, pos, cfg)
    k = positioned(k, pos, cfg)

    slots = cache["k"].shape[1]
    idx = (positions % slots).astype(jnp.int32)            # (C,)
    new_cache: Dict[str, jax.Array] = {}
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        entries = (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs))
    else:
        entries = (("k", k), ("v", v))
    for name, val in entries:
        new_cache[name] = cache[name].at[:, idx].set(val)
    if cfg.kv_cache_dtype == "int8":
        k_cache = (new_cache["k"].astype(jnp.float32)
                   * new_cache["k_scale"]).astype(x.dtype)
        v_cache = (new_cache["v"].astype(jnp.float32)
                   * new_cache["v_scale"]).astype(x.dtype)
    else:
        k_cache, v_cache = new_cache["k"], new_cache["v"]

    k_positions = _cache_abs_positions(positions[-1], slots,
                                       cfg.sliding_window, b)
    out = naive_attention(q, k_cache, v_cache, causal=True,
                          window=cfg.sliding_window,
                          q_positions=pos, k_positions=k_positions,
                          scale=softmax_scale(cfg))
    out = out.reshape(b, c, cfg.q_dim)
    out = lora_dense(out, params["wo"], maybe_lora(lora, "wo"), scale,
                     None, uk)
    return out, new_cache
