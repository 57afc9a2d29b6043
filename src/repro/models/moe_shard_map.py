"""Expert-parallel MoE via shard_map + all_to_all — the TPU-native dispatch.

GSPMD cannot shard a data-dependent scatter across expert shards (it
replicates the dispatch, which the dry-run exposed as ~100 TB/device of HBO
traffic for Kimi-K2). This module expresses the paper-relevant MoE layers
with explicit collectives instead:

  Layout ('ep_a2a'): expert weights sharded E over the data-parallel axes
  (EP) and FFN width f over 'model' (TP). Per layer:

    source shard --(all_to_all over dp)--> expert owner
      local capacity dispatch -> expert FFN on the f-slice
    expert owner --(all_to_all back)--> source shard
      combine with gates; one psum over 'model' merges the TP-partial
      down-projections (the shared expert folds into the same psum).

  Per-device weights for kimi-k2 (2x16x16): 384/32 experts x f/16 — ~4 GB of
  the 2 TB backbone: this is what makes the 1T config fit 16 GB HBM chips.

  Layout ('replicated'): small MoEs (granite-3b: ~3 GB of experts, 40
  experts indivisible by 16) replicate expert weights and dispatch purely
  locally per data shard — zero intra-MoE collectives.

Routing is computed identically on every TP column (activations are
replicated across 'model'), so each column runs the same a2a — see
EXPERIMENTS.md §Perf for the payload-slicing optimization over this.

Differentiable end-to-end (all_to_all/psum have transpose rules; scatter
indices are integer-valued and constant w.r.t. the tangent).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import shardctx
from repro.configs.base import ModelConfig
from repro.models.common import ACC_DTYPE, Params, silu
from repro.models.moe import group_capacity, ranks_within_groups

def select_strategy(cfg: ModelConfig) -> Optional[str]:
    """Pick the distributed MoE layout for the active mesh (None => jnp/GSPMD
    path, used on CPU and single-device tests)."""
    if not shardctx.active() or not cfg.is_moe:
        return None
    dp = shardctx.axis_size("dp")
    tp = shardctx.axis_size("model")
    if dp > 1 and cfg.n_experts % dp == 0 and cfg.d_ff % tp == 0:
        return "ep_a2a"
    expert_bytes = (cfg.n_experts + cfg.n_shared_experts) * 3 \
        * cfg.d_model * cfg.d_ff * 2
    if expert_bytes <= 6e9:
        return "replicated"
    return None


def strategy_for_mesh(cfg: ModelConfig, mesh) -> Optional[str]:
    """Same decision from a mesh object (for sharding.param_specs)."""
    with shardctx.mesh_ctx(mesh):
        return select_strategy(cfg)


def _group_index(dp_axes: Tuple[str, ...], mesh) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for a in dp_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _expert_ffn(buf, wg, wu, wd, dtype):
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype),
                   preferred_element_type=ACC_DTYPE).astype(dtype)
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dtype),
                   preferred_element_type=ACC_DTYPE).astype(dtype)
    h = silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype),
                      preferred_element_type=ACC_DTYPE).astype(dtype)


def _shared_ffn(xf, shared, dtype):
    sg = jnp.matmul(xf, shared["w_gate"].astype(dtype),
                    preferred_element_type=ACC_DTYPE).astype(dtype)
    su = jnp.matmul(xf, shared["w_up"].astype(dtype),
                    preferred_element_type=ACC_DTYPE).astype(dtype)
    return jnp.matmul(silu(sg) * su, shared["w_down"].astype(dtype),
                      preferred_element_type=ACC_DTYPE).astype(dtype)


def _route(xf, router, cfg):
    logits = jnp.matmul(xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss, local shard contribution
    me = jnp.mean(probs, axis=0)
    ce = jnp.bincount(idx[:, 0], length=cfg.n_experts
                      ).astype(jnp.float32) / xf.shape[0]
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.router_aux_coef
    return gates, idx, aux


# ---------------------------------------------------------------------------
# EP + a2a layout
# ---------------------------------------------------------------------------


def _local_moe_ep(x_blk, router, wg, wu, wd, shared, *, cfg: ModelConfig,
                  dp_axes, mesh):
    ep = 1
    for a in dp_axes:
        ep *= mesh.shape[a]
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    eg = e // ep
    b_loc, s, _ = x_blk.shape
    t_loc = b_loc * s
    xf = x_blk.reshape(t_loc, d)
    dtype = x_blk.dtype

    gates, idx, aux = _route(xf, router, cfg)
    flat_e = idx.reshape(-1)                      # (n,) n = t_loc*k
    n = flat_e.shape[0]

    # ---- send-side packing by destination expert group --------------------
    dest = flat_e // eg
    cs = group_capacity(n, ep, cfg.capacity_factor)
    pos_s = ranks_within_groups(dest, ep)
    keep_s = pos_s < cs
    ps = jnp.where(keep_s, pos_s, 0)
    tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
    payload = jnp.where(keep_s[:, None], xf[tok], 0).astype(dtype)
    send_x = jnp.zeros((ep, cs, d), dtype).at[dest, ps].add(payload)
    send_e = jnp.zeros((ep, cs), jnp.int32).at[dest, ps].add(
        jnp.where(keep_s, flat_e + 1, 0))         # 0 == empty slot

    # ---- the MoE all-to-all ------------------------------------------------
    recv_x = jax.lax.all_to_all(send_x, dp_axes, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, dp_axes, 0, 0, tiled=True)

    # ---- receiver: dispatch to local experts ------------------------------
    g_idx = _group_index(dp_axes, mesh)
    rx = recv_x.reshape(ep * cs, d)
    re_ = recv_e.reshape(ep * cs) - 1
    le = re_ - g_idx * eg
    valid = re_ >= 0
    le_sort = jnp.where(valid, le, eg)            # invalid -> trash group
    cr = group_capacity(ep * cs, eg, cfg.capacity_factor)
    pos_r = ranks_within_groups(le_sort, eg + 1)
    keep_r = valid & (pos_r < cr)
    lec = jnp.where(keep_r, le, 0)
    pr = jnp.where(keep_r, pos_r, 0)
    buf = jnp.zeros((eg, cr, d), dtype).at[lec, pr].add(
        jnp.where(keep_r[:, None], rx, 0).astype(dtype))

    # ---- expert FFN on the local f-slice (TP partial) ----------------------
    y = _expert_ffn(buf, wg, wu, wd, dtype)

    # ---- return trip --------------------------------------------------------
    y_rows = jnp.where(keep_r[:, None], y[lec, pr], 0).reshape(ep, cs, d)
    back = jax.lax.all_to_all(y_rows, dp_axes, 0, 0, tiled=True)

    # ---- combine at the source ----------------------------------------------
    contrib = back[dest, ps] * (gates.reshape(-1)
                                * keep_s)[:, None].astype(dtype)
    out = jnp.zeros((t_loc, d), dtype).at[tok].add(contrib)
    if shared is not None:
        out = out + _shared_ffn(xf, shared, dtype)

    # merge TP-partial contributions (expert down-proj + shared down-proj)
    out = jax.lax.psum(out, "model")
    aux = jax.lax.pmean(aux, dp_axes)
    return out.reshape(b_loc, s, d), aux


# ---------------------------------------------------------------------------
# EP with broadcast tokens (decode with batch too small to shard, e.g.
# long_500k batch=1): tokens replicated; each device serves only its local
# expert slice; one psum over (dp + model) merges expert groups and TP.
# ---------------------------------------------------------------------------


def _local_moe_ep_bcast(x_blk, router, wg, wu, wd, shared, *,
                        cfg: ModelConfig, dp_axes, mesh):
    ep = 1
    for a in dp_axes:
        ep *= mesh.shape[a]
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    eg = e // ep
    b, s, _ = x_blk.shape
    t = b * s
    xf = x_blk.reshape(t, d)
    dtype = x_blk.dtype
    gates, idx, aux = _route(xf, router, cfg)
    flat_e = idx.reshape(-1)
    n = flat_e.shape[0]
    g_idx = _group_index(dp_axes, mesh)
    le = flat_e - g_idx * eg
    mine = (le >= 0) & (le < eg)
    cr = group_capacity(n, eg, max(cfg.capacity_factor, float(eg)))
    le_sort = jnp.where(mine, le, eg)
    pos = ranks_within_groups(le_sort, eg + 1)
    keep = mine & (pos < cr)
    lec = jnp.where(keep, le, 0)
    pr = jnp.where(keep, pos, 0)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((eg, cr, d), dtype).at[lec, pr].add(
        jnp.where(keep[:, None], xf[tok], 0).astype(dtype))
    y = _expert_ffn(buf, wg, wu, wd, dtype)
    contrib = y[lec, pr] * (gates.reshape(-1) * keep)[:, None].astype(dtype)
    out = jnp.zeros((t, d), dtype).at[tok].add(contrib)
    if shared is not None:
        # every dp shard computes the same f-slice: pre-divide so the joint
        # psum over (dp, model) counts each f-slice exactly once
        out = out + _shared_ffn(xf, shared, dtype) / ep
    out = jax.lax.psum(out, tuple(dp_axes) + ("model",))
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Replicated-experts layout (small MoEs / indivisible expert counts)
# ---------------------------------------------------------------------------


def _local_moe_replicated(x_blk, router, wg, wu, wd, shared, *,
                          cfg: ModelConfig, dp_axes, mesh):
    from repro.models.moe import _capacity
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    b_loc, s, _ = x_blk.shape
    t_loc = b_loc * s
    xf = x_blk.reshape(t_loc, d)
    dtype = x_blk.dtype
    gates, idx, aux = _route(xf, router, cfg)
    flat_e = idx.reshape(-1)
    cap = _capacity(t_loc, cfg)
    pos = ranks_within_groups(flat_e, e)
    keep = pos < cap
    pc = jnp.where(keep, pos, 0)
    tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
    buf = jnp.zeros((e, cap, d), dtype).at[flat_e, pc].add(
        jnp.where(keep[:, None], xf[tok], 0).astype(dtype))
    y = _expert_ffn(buf, wg, wu, wd, dtype)
    contrib = y[flat_e, pc] * (gates.reshape(-1) * keep)[:, None].astype(dtype)
    out = jnp.zeros((t_loc, d), dtype).at[tok].add(contrib)
    if shared is not None:
        out = out + _shared_ffn(xf, shared, dtype)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return out.reshape(b_loc, s, d), aux


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def moe_forward_dist(params: Params, lora: Optional[Params], x: jax.Array,
                     cfg: ModelConfig, strategy: str
                     ) -> Tuple[jax.Array, jax.Array]:
    """Distributed MoE layer. x: (B, S, d) GSPMD-sharded P(dp, None, None)."""
    mesh = shardctx.mesh()
    dp = shardctx.dp_axes()
    dp_size = shardctx.axis_size("dp")
    batch_shardable = x.shape[0] % dp_size == 0

    if strategy == "replicated" and not batch_shardable:
        # weights replicated anyway: plain jnp path is already correct
        from repro.models.moe import moe_forward
        return moe_forward(params, lora, x, cfg)

    if strategy == "ep_a2a":
        local = _local_moe_ep if batch_shardable else _local_moe_ep_bcast
        wspec = (P(dp, None, "model"), P(dp, None, "model"),
                 P(dp, "model", None))
        shared_spec = {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                       "w_down": P("model", None)}
    else:
        # replicated experts: tokens shard over dp AND the (otherwise idle)
        # TP axis — without this every TP column redundantly computed the
        # same dispatch (measured 16x compute waste on granite; §Perf-4)
        tp = mesh.shape.get("model", 1)
        if x.shape[0] % (dp_size * tp) == 0:
            dp = tuple(dp) + ("model",)
        local = _local_moe_replicated
        wspec = (P(None, None, None),) * 3
        shared_spec = {"w_gate": P(None, None), "w_up": P(None, None),
                       "w_down": P(None, None)}
    fn = functools.partial(local, cfg=cfg, dp_axes=dp, mesh=mesh)

    xspec = P(dp, None, None) if batch_shardable else P(None, None, None)
    shared = params.get("shared")
    in_specs = (xspec, P(None, None), *wspec,
                shared_spec if shared is not None else None)
    out_specs = (xspec, P())

    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    out, aux = mapped(x, params["router"], params["w_gate"], params["w_up"],
                      params["w_down"], shared)

    if lora is not None:  # shared-path adapter (DESIGN.md), outside the map
        la = lora["out_adapter"]
        adapt = jnp.matmul(
            jnp.matmul(x, la["a"].astype(x.dtype),
                       preferred_element_type=ACC_DTYPE).astype(x.dtype),
            la["b"].astype(x.dtype), preferred_element_type=ACC_DTYPE)
        out = out + cfg.lora.scale * adapt.astype(x.dtype)
    return out, aux.astype(jnp.float32)
