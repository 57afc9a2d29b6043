"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs in Python per grid cell, which validates the exact TPU
program logic. Every other backend compiles them to Mosaic, and fails if
they do not compile: a kernel never runs interpreted on an accelerator.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import lora_matmul as _lm
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def lora_matmul(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array,
                scale: float = 1.0, **block_kw) -> jax.Array:
    """Fused y = x @ W + scale*(x @ A) @ B. Leading dims of x are flattened."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _lm.lora_matmul(x2, w, a, b, scale, interpret=_interpret(), **block_kw)
    return y.reshape(*lead, w.shape[-1])


def lora_matmul_grouped(x: jax.Array, w: jax.Array, a: jax.Array,
                        b: jax.Array, ids: jax.Array, scale: float = 1.0,
                        **block_kw) -> jax.Array:
    """Multi-tenant fused LoRA: y[g] = x[g] @ W + scale*(x[g] @ A[ids[g]])
    @ B[ids[g]]. x: (G, M, K) or (G, K); a: (E, K, r); b: (E, r, N);
    ids: (G,) int32 adapter index per request row."""
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
    y = _lm.lora_matmul_grouped(x, w, a, b, jnp.asarray(ids, jnp.int32),
                                scale, interpret=_interpret(), **block_kw)
    return y[:, 0] if squeeze else y


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    q_positions=None, k_positions=None,
                    **block_kw) -> jax.Array:
    """GQA-aware wrapper. q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D).
    ``scale``: the softmax scale, 1/sqrt(D) if None. Differentiable.

    The kernel assumes positions are 0..S-1; generalized position vectors
    (ring-buffer decode) stay on the jnp path in models/attention.py.
    """
    b, sq, hq, d = q.shape
    # heads into the kernel's leading dim, batch-major: query row
    # b * hq + h reads KV row b * hkv + h // group, its group's KV head
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], d)
    out = _fa.flash_attention(fold(q), fold(k), fold(v), causal=causal,
                              window=window, sm_scale=scale,
                              interpret=_interpret(), **block_kw)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 t, *, window: int = 0, **block_kw) -> jax.Array:
    """One-token GQA decode attention. q: (B, 1, Hq, D); caches:
    (B, S, Hkv, D). Returns (B, 1, Hq, D). Ring-buffer SWA caches use
    window == slots semantics (models/attention.py)."""
    b, _, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    qf = q[:, 0].reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out = _fd.flash_decode(qf, kf, vf, t, window=window,
                           interpret=_interpret(), **block_kw)
    return out.reshape(b, hkv, group, d).reshape(b, 1, hq, d)


def ssd_scan(xt: jax.Array, a: jax.Array, B: jax.Array, C: jax.Array,
             chunk: int, h0: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Full SSD scan using the Pallas intra-chunk kernel + jnp inter-chunk
    recurrence. Same contract as models.mamba.ssd_chunked:
    xt: (B, L, nh, hp); a: (B, L, nh); B, C: (B, L, ns).
    Returns (y: (B, L, nh, hp) f32, h_final: (B, nh, hp, ns) f32).
    """
    b, l, nh, hp = xt.shape
    ns = B.shape[-1]
    pad = (-l) % chunk
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    lp = xt.shape[1]
    nc = lp // chunk
    xt_c = xt.reshape(b, nc, chunk, nh, hp)
    a_c = a.reshape(b, nc, chunk, nh)
    B_c = B.reshape(b, nc, chunk, ns)
    C_c = C.reshape(b, nc, chunk, ns)

    y_diag, states, dec = _ssd.ssd_intra_chunk(
        xt_c, a_c, B_c, C_c, interpret=_interpret())
    # states: (b, nc, nh, ns, hp) -> match (b, nc, nh, hp, ns)
    states = states.transpose(0, 1, 2, 4, 3)

    if h0 is None:
        h0 = jnp.zeros((b, nh, hp, ns), jnp.float32)
    a_tot = dec[:, :, -1, :, 1]                        # (b, nc, nh) total decay

    def step(h, inp):
        at, st = inp
        return h * at[:, :, None, None] + st, h

    h_final, h_prevs = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (a_tot.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)         # (b, nc, nh, hp, ns)

    # cross-chunk correction: C_i . h_prev * exp(cum_i)
    y_off = jnp.einsum("bcin,bcihpn->bcihp", C_c.astype(jnp.float32),
                       dec[..., 0][..., None, None] * h_prevs[:, :, None])
    y = (y_diag + y_off).reshape(b, lp, nh, hp)
    return y[:, :l], h_final
