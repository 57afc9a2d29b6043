"""Mamba-2 (SSD, arXiv:2405.21060) block: chunked parallel scan for
train/prefill, O(1)-state recurrence for decode.

Single B/C group (ngroups=1), head structure (nh heads x hp head_dim).
The chunked SSD math here is the pure-jnp oracle shared with
``repro.kernels.ssd_scan``; the Pallas kernel implements the intra-chunk
part with VMEM tiling.

The full-sequence mixer runs under the named scope ``ssm.mixer`` and its
SSD scan under ``ssm.scan``; both reach the compiled program's op
metadata, the backward pass under ``transpose(...)``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (ACC_DTYPE, Params, dense_init,
                                 init_lora_pair, init_rms_norm, lora_dense,
                                 maybe_lora, rms_norm, silu)

SCOPE_SSM_MIXER = "ssm.mixer"   # in_proj through out_proj
SCOPE_SSM_SCAN = "ssm.scan"     # the SSD scan inside it


def init_mamba(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
    conv_ch = di + 2 * ns
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[2], (nh,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        # in_proj -> [z(di), x(di), B(ns), C(ns), dt(nh)]
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * ns + nh, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_ch),
                                     jnp.float32) / math.sqrt(cfg.ssm_conv_width)
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "gate_norm": init_rms_norm(di),
        "out_proj": dense_init(ks[3], di, d, dtype),
    }


def init_mamba_lora(key, cfg: ModelConfig) -> Params:
    r, d, di = cfg.lora.rank, cfg.d_model, cfg.ssm_d_inner
    ldt = jnp.dtype(cfg.lora.dtype)
    k1, k2 = jax.random.split(key)
    return {
        "in_proj": init_lora_pair(k1, d, 2 * di + 2 * cfg.ssm_state
                                  + cfg.ssm_n_heads, r, ldt),
        "out_proj": init_lora_pair(k2, di, d, r, ldt),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 prefix: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv. xbc: (B,L,C); w: (W,C). prefix: (B,W-1,C)."""
    width = w.shape[0]
    if prefix is None:
        prefix = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    xp = jnp.concatenate([prefix, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i] for i in range(width))
    return silu(out + b)


def ssd_chunked(xt: jax.Array, a: jax.Array, B: jax.Array, C: jax.Array,
                chunk: int, h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD scan. xt: (B,L,nh,hp) pre-multiplied by dt; a: (B,L,nh) = A*dt
    (<=0); B,C: (B,L,ns). Returns (y: (B,L,nh,hp), h_final: (B,nh,hp,ns)).

    Every contraction is pairwise, so no tensor holds a state (hp x ns) per
    position or an (i, j) pair per head dimension: the largest terms are
    the intra-chunk weights (b, nc, chunk, chunk, nh) and one state per
    chunk (b, nc, nh, hp, ns)."""
    b, l, nh, hp = xt.shape
    ns = B.shape[-1]
    pad = (-l) % chunk
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nc = xt.shape[1] // chunk
    xt = xt.reshape(b, nc, chunk, nh, hp).astype(ACC_DTYPE)
    a = a.reshape(b, nc, chunk, nh).astype(ACC_DTYPE)
    Bc = B.reshape(b, nc, chunk, ns).astype(ACC_DTYPE)
    Cc = C.reshape(b, nc, chunk, ns).astype(ACC_DTYPE)

    cum = jnp.cumsum(a, axis=2)                          # (b,nc,cl,nh)
    # intra-chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) x_j; the
    # mask goes in before exp, so no masked entry overflows (nor its
    # gradient)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,i,j,nh)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)       # (b,nc,i,j)
    weights = scores[..., None] * decay                  # (b,nc,i,j,nh)
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", weights, xt)

    # chunk-final states: x weighted by its decay to the chunk's end, then
    # contracted with B over the chunk's positions
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (b,nc,cl,nh)
    states = jnp.einsum("bcjn,bcjhp->bchpn", Bc, xt * dec_end[..., None])

    # inter-chunk recurrence
    a_tot = jnp.exp(cum[:, :, -1, :])                    # (b,nc,nh)
    if h0 is None:
        h0 = jnp.zeros((b, nh, hp, ns), ACC_DTYPE)
    else:
        h0 = h0.astype(ACC_DTYPE)

    def step(h, inp):
        at, st = inp                                     # (b,nh),(b,nh,hp,ns)
        h_new = h * at[:, :, None, None] + st
        return h_new, h

    h_final, h_prevs = jax.lax.scan(
        step, h0, (a_tot.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)           # (b,nc,nh,hp,ns)

    # the state entering each chunk, read by C, decayed to each position
    y_off = (jnp.einsum("bcin,bchpn->bcihp", Cc, h_prevs)
             * jnp.exp(cum)[..., None])
    y = (y_diag + y_off).reshape(b, nc * chunk, nh, hp)
    return y[:, :l], h_final


def mamba_forward(params: Params, lora: Optional[Params], x: jax.Array,
                  cfg: ModelConfig, use_lora_kernel: bool = False
                  ) -> jax.Array:
    """Full-sequence forward. x: (B,L,d) -> (B,L,d)."""
    with jax.named_scope(SCOPE_SSM_MIXER):
        di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
        hp = cfg.ssm_head_dim
        proj = lora_dense(x, params["in_proj"], maybe_lora(lora, "in_proj"),
                          cfg.lora.scale, use_kernel=use_lora_kernel)
        z, xs, B, C, dt_raw = jnp.split(
            proj, [di, 2 * di, 2 * di + ns, 2 * di + 2 * ns], axis=-1)
        xbc = _causal_conv(jnp.concatenate([xs, B, C], -1),
                           params["conv_w"], params["conv_b"])
        xs, B, C = jnp.split(xbc, [di, di + ns], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        A = -jnp.exp(params["a_log"])                    # (nh,)
        bsz, l = x.shape[0], x.shape[1]
        xh = xs.reshape(bsz, l, nh, hp)
        xt = xh.astype(ACC_DTYPE) * dt[..., None]
        a = dt * A
        with jax.named_scope(SCOPE_SSM_SCAN):
            y, _ = ssd_chunked(xt, a, B, C, cfg.ssm_chunk)
        y = y + params["d_skip"][:, None] * xh.astype(ACC_DTYPE)
        y = y.reshape(bsz, l, di).astype(x.dtype)
        y = rms_norm(y * silu(z), params["gate_norm"], cfg.rms_eps)
        return lora_dense(y, params["out_proj"],
                          maybe_lora(lora, "out_proj"), cfg.lora.scale,
                          use_kernel=use_lora_kernel)


# ---------------------------------------------------------------------------
# Decode (recurrent, O(1) per token)
# ---------------------------------------------------------------------------


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
    hp = cfg.ssm_head_dim
    return {
        "h": jnp.zeros((batch, nh, hp, ns), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, di + 2 * ns), dtype),
    }


def mamba_decode(params: Params, lora: Optional[Params], x: jax.Array,
                 cache: Dict[str, jax.Array], cfg: ModelConfig
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B,1,d) -> (y: (B,1,d), new cache)."""
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
    hp = cfg.ssm_head_dim
    proj = lora_dense(x, params["in_proj"], maybe_lora(lora, "in_proj"),
                      cfg.lora.scale)
    z, xs, B, C, dt_raw = jnp.split(
        proj, [di, 2 * di, 2 * di + ns, 2 * di + 2 * ns], axis=-1)
    xbc_t = jnp.concatenate([xs, B, C], -1)              # (B,1,conv_ch)
    conv_in = jnp.concatenate([cache["conv"], xbc_t], axis=1)
    w = params["conv_w"]
    out = sum(conv_in[:, i:i + 1] * w[i] for i in range(w.shape[0]))
    xbc = silu(out + params["conv_b"])                   # (B,1,conv_ch)
    new_conv = conv_in[:, 1:]
    xs, B, C = jnp.split(xbc, [di, di + ns], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])[:, 0]
    A = -jnp.exp(params["a_log"])
    a = jnp.exp(dt * A)                                  # (B,nh)
    xh = xs[:, 0].reshape(-1, nh, hp).astype(jnp.float32)
    xt = xh * dt[..., None]
    Bv, Cv = B[:, 0].astype(jnp.float32), C[:, 0].astype(jnp.float32)
    h = (cache["h"] * a[:, :, None, None]
         + jnp.einsum("bhp,bn->bhpn", xt, Bv))
    y = jnp.einsum("bhpn,bn->bhp", h, Cv) + params["d_skip"][:, None] * xh
    y = y.reshape(-1, 1, di).astype(x.dtype)
    y = rms_norm(y * silu(z), params["gate_norm"], cfg.rms_eps)
    y = lora_dense(y, params["out_proj"], maybe_lora(lora, "out_proj"),
                   cfg.lora.scale)
    return y, {"h": h, "conv": new_conv}
