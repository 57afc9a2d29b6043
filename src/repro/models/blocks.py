"""Per-family layer blocks (pre-norm residual), stacked for lax.scan.

An ``ssm`` layer with ``d_ff > 0`` (a mixed stack's Mamba layer) follows
its mixer with the MLP, as an attention layer does. Every block output is
scaled by ``residual_multiplier`` before it joins the residual stream."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import Params, init_rms_norm, rms_norm

ATTN_FAMILIES = ("dense", "moe", "hybrid", "audio", "vlm")


def _residual(x: jax.Array, out: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.residual_multiplier == 1.0:
        return x + out
    return x + out * cfg.residual_multiplier


def init_layer(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {"norm1": init_rms_norm(cfg.d_model)}
    if cfg.family == "ssm":
        p["mamba"] = mamba_mod.init_mamba(ks[0], cfg, dtype)
        if cfg.d_ff:
            p["norm2"] = init_rms_norm(cfg.d_model)
            p["mlp"] = mlp_mod.init_mlp(ks[1], cfg, dtype)
        return p
    p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
    p["norm2"] = init_rms_norm(cfg.d_model)
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_mod.init_mlp(ks[1], cfg, dtype)
    if cfg.family == "hybrid":
        p["mamba"] = mamba_mod.init_mamba(ks[2], cfg, dtype)
    return p


def init_layer_lora(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {}
    if cfg.family == "ssm":
        p["mamba"] = mamba_mod.init_mamba_lora(ks[0], cfg)
        if cfg.d_ff:
            p["mlp"] = mlp_mod.init_mlp_lora(ks[1], cfg)
        return p
    p["attn"] = attn_mod.init_attention_lora(ks[0], cfg)
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe_lora(ks[1], cfg)
    else:
        p["mlp"] = mlp_mod.init_mlp_lora(ks[1], cfg)
    if cfg.family == "hybrid":
        p["mamba"] = mamba_mod.init_mamba_lora(ks[2], cfg)
    return p


def layer_forward(params: Params, lora: Optional[Params], x: jax.Array,
                  cfg: ModelConfig, *, positions: jax.Array,
                  impl: str = "chunked", use_lora_kernel: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    lget = (lambda k: lora.get(k) if lora is not None else None)
    h = rms_norm(x, params["norm1"], cfg.rms_eps)
    if cfg.family == "ssm":
        x = _residual(x, mamba_mod.mamba_forward(
            params["mamba"], lget("mamba"), h, cfg, use_lora_kernel), cfg)
        if not cfg.d_ff:
            return x, aux
    else:
        attn_out, _ = attn_mod.attention_forward(
            params["attn"], lget("attn"), h, cfg, positions=positions,
            impl=impl, use_lora_kernel=use_lora_kernel)
        if cfg.family == "hybrid":
            ssm_out = mamba_mod.mamba_forward(params["mamba"], lget("mamba"),
                                              h, cfg, use_lora_kernel)
            x = x + 0.5 * (attn_out + ssm_out)
        else:
            x = _residual(x, attn_out, cfg)
    h2 = rms_norm(x, params["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        moe_out, aux = _moe_dispatch(params["moe"], lget("moe"), h2, cfg,
                                     use_lora_kernel)
        x = x + moe_out
    else:
        x = _residual(x, mlp_mod.mlp_forward(params["mlp"], lget("mlp"), h2,
                                             cfg, use_lora_kernel), cfg)
    return x, aux


def _moe_dispatch(params: Params, lora, h: jax.Array, cfg: ModelConfig,
                  use_lora_kernel: bool):
    """Route to the shard_map expert-parallel path when a mesh is active."""
    from repro.models import moe_shard_map
    strategy = moe_shard_map.select_strategy(cfg)
    if strategy is not None:
        return moe_shard_map.moe_forward_dist(params, lora, h, cfg, strategy)
    return moe_mod.moe_forward(params, lora, h, cfg, use_lora_kernel)


def init_layer_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    c: Params = {}
    if cfg.family != "ssm":
        c["kv"] = attn_mod.init_kv_cache(cfg, batch, max_len, dtype)
    if cfg.has_ssm:
        c["ssm"] = mamba_mod.init_ssm_cache(cfg, batch, dtype)
    return c


def layer_prefill(params: Params, lora: Optional[Params], x: jax.Array,
                  cache: Params, cfg: ModelConfig, *, positions: jax.Array,
                  use_lora_kernel: bool = False) -> Tuple[jax.Array, Params]:
    """Cache-writing multi-token prefill through a layer. x: (B,C,d);
    ``positions``: (C,) absolute positions of the chunk.

    Attention-only families — SSM-bearing configs carry cumulative
    recurrent state and go through the exact ``model.decode_scan`` path
    instead (dispatched at the model level).
    """
    lget = (lambda k: lora.get(k) if lora is not None else None)
    new_cache: Params = {}
    h = rms_norm(x, params["norm1"], cfg.rms_eps)
    attn_out, new_cache["kv"] = attn_mod.attention_prefill(
        params["attn"], lget("attn"), h, cache["kv"], cfg, positions=positions,
        use_lora_kernel=use_lora_kernel)
    x = _residual(x, attn_out, cfg)
    h2 = rms_norm(x, params["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        moe_out, _ = _moe_dispatch(params["moe"], lget("moe"), h2, cfg,
                                   use_lora_kernel)
        x = x + moe_out
    else:
        x = _residual(x, mlp_mod.mlp_forward(params["mlp"], lget("mlp"), h2,
                                             cfg, use_lora_kernel), cfg)
    return x, new_cache


def layer_decode(params: Params, lora: Optional[Params], x: jax.Array,
                 cache: Params, cfg: ModelConfig, *, t: jax.Array,
                 use_lora_kernel: bool = False) -> Tuple[jax.Array, Params]:
    """One-token decode through a layer. x: (B,1,d)."""
    lget = (lambda k: lora.get(k) if lora is not None else None)
    new_cache: Params = {}
    h = rms_norm(x, params["norm1"], cfg.rms_eps)
    if cfg.family == "ssm":
        out, new_cache["ssm"] = mamba_mod.mamba_decode(
            params["mamba"], lget("mamba"), h, cache["ssm"], cfg)
        x = _residual(x, out, cfg)
        if not cfg.d_ff:
            return x, new_cache
    else:
        attn_out, new_cache["kv"] = attn_mod.attention_decode(
            params["attn"], lget("attn"), h, cache["kv"], cfg, t=t,
            use_lora_kernel=use_lora_kernel)
        if cfg.family == "hybrid":
            ssm_out, new_cache["ssm"] = mamba_mod.mamba_decode(
                params["mamba"], lget("mamba"), h, cache["ssm"], cfg)
            x = x + 0.5 * (attn_out + ssm_out)
        else:
            x = _residual(x, attn_out, cfg)
    h2 = rms_norm(x, params["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        moe_out, _ = _moe_dispatch(params["moe"], lget("moe"), h2, cfg,
                                   use_lora_kernel)
        x = x + moe_out
    else:
        x = _residual(x, mlp_mod.mlp_forward(params["mlp"], lget("mlp"), h2,
                                             cfg, use_lora_kernel), cfg)
    return x, new_cache
