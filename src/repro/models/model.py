"""Full model assembly: init, split-aware forward, loss, cached decode.

Parameters come in two trees:
  * ``frozen`` — the pre-trained backbone (never receives gradients);
  * ``lora``   — the trainable adapters (paper: only A/B matrices train).

Layer params are stacked along a leading ``n_layers`` axis and executed with
``jax.lax.scan`` (+ optional remat), which keeps the HLO size independent of
depth — essential for lowering the 61-layer / 1T-param configs.

A mixed stack (``cfg.layer_types``) keeps one such stack per layer kind,
``layers[kind]``, and runs layers ``[lo, hi)`` as consecutive runs of one
kind, each a scan over its rows of that kind's stack (``layer_runs``).

Split learning support: ``forward_hidden(..., lo, hi)`` runs layers
``[lo, hi)`` only. ``lo == 0`` includes the embedding; ``hi == n_layers``
is the natural server end (final norm + LM head live with the loss).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.common import (ACC_DTYPE, Params, dtype_of, embed_init,
                                 init_rms_norm, rms_norm,
                                 softmax_cross_entropy)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    """Full parameter tree {"frozen": ..., "lora": ...}."""
    dtype = dtype_of(cfg.dtype)
    k_embed, k_head, k_layers, k_lora = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    lora_keys = jax.random.split(k_lora, cfg.n_layers)

    def stacks(init, keys):
        if not cfg.layer_types:
            return jax.vmap(lambda k: init(k, cfg))(keys)
        return {kind: jax.vmap(lambda k, c=cfg.kind_config(kind): init(k, c))(
                    keys[jnp.asarray([i for i, t in enumerate(cfg.layer_types)
                                      if t == kind])])
                for kind in cfg.kind_counts(0, cfg.n_layers)}

    layers = stacks(lambda k, c: blocks.init_layer(k, c, dtype), layer_keys)
    lora_layers = stacks(blocks.init_layer_lora, lora_keys)
    frozen: Params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        frozen["head"] = embed_init(k_head, cfg.padded_vocab, cfg.d_model,
                                    dtype).T
    return {"frozen": frozen, "lora": {"layers": lora_layers}}


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct tree — no allocation (dry-run path for 1T params)."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def slice_layers(tree: Params, lo: int, hi: int) -> Params:
    return jax.tree_util.tree_map(lambda x: x[lo:hi], tree)


def layer_runs(cfg: ModelConfig, lo: int, hi: int) -> List[Tuple[str, int, int]]:
    """Layers [lo, hi) as consecutive runs of one kind, in order:
    ``(kind, start, stop)`` with start and stop indexing that kind's
    stack. A uniform stack is one run of its family."""
    runs: List[Tuple[str, int, int]] = []
    for i in range(lo, hi):
        kind = cfg.layer_kinds[i]
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
        else:
            j = cfg.kind_index(kind, i)
            runs.append((kind, j, j + 1))
    return runs


def kind_stack(cfg: ModelConfig, layers: Params, kind: str) -> Params:
    """The stacked layers of ``kind`` (all of them, in a uniform stack)."""
    return layers[kind] if cfg.layer_types else layers


def slice_stack(cfg: ModelConfig, layers: Params, lo: int, hi: int) -> Params:
    """The rows of a (per-kind) layer stack that hold layers [lo, hi)."""
    if not cfg.layer_types:
        return slice_layers(layers, lo, hi)
    return {kind: slice_layers(stack, cfg.kind_index(kind, lo),
                               cfg.kind_index(kind, hi))
            for kind, stack in layers.items()}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def embed_inputs(frozen: Params, batch_inputs: jax.Array, cfg: ModelConfig
                 ) -> jax.Array:
    """tokens (B,S) int32 -> (B,S,d), times ``embedding_multiplier``; or
    pass-through for 'embeds' mode."""
    if cfg.input_mode == "embeds":
        return batch_inputs.astype(dtype_of(cfg.dtype))
    x = jnp.take(frozen["embed"], batch_inputs, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def forward_hidden(frozen: Params, lora: Optional[Params], inputs: jax.Array,
                   cfg: ModelConfig, *, lo: int = 0, hi: Optional[int] = None,
                   positions: Optional[jax.Array] = None,
                   impl: str = "chunked", remat: bool = True,
                   use_lora_kernel: bool = False,
                   inputs_embedded: Optional[bool] = None,
                   lora_sliced: bool = False,
                   unroll: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Run layers [lo, hi). By default ``lo==0`` means ``inputs`` are
    tokens/embeds and the embedding is applied; otherwise ``inputs`` are
    hidden states (smashed data). ``inputs_embedded=True`` forces the
    hidden-state interpretation (server stage at cut 0).
    Returns (hidden, aux_loss_sum)."""
    hi = cfg.n_layers if hi is None else hi
    if inputs_embedded is None:
        inputs_embedded = lo != 0
    if not inputs_embedded:
        x = embed_inputs(frozen, inputs, cfg)
    else:
        x = inputs
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
    elif impl == "pallas":
        impl = "chunked"      # the kernels assume positions 0..S-1

    carry = (x, jnp.zeros((), jnp.float32))
    for kind, start, stop in layer_runs(cfg, lo, hi):
        layer_params = _rows(kind_stack(cfg, frozen["layers"], kind),
                             start, stop)
        if lora is None:
            layer_lora = None
        else:  # a caller may hold exactly the [lo,hi) adapters already
            first = cfg.kind_index(kind, lo) if lora_sliced else 0
            layer_lora = _rows(kind_stack(cfg, lora["layers"], kind),
                               start - first, stop - first)
        carry = _run_layers(carry, layer_params, layer_lora,
                            cfg.kind_config(kind), positions=positions,
                            impl=impl, remat=remat,
                            use_lora_kernel=use_lora_kernel, unroll=unroll)
    return carry


class _Rows:
    """Rows [start, stop) of a layer stack, read one at a time inside the
    loop over layers: slicing them out first would copy their weights."""

    def __init__(self, stack: Params, start: int, stop: int):
        self.stack = stack
        self.index = jnp.arange(start, stop, dtype=jnp.int32)

    def row(self, i) -> Params:
        return jax.tree_util.tree_map(
            lambda v: jax.lax.dynamic_index_in_dim(v, i, keepdims=False),
            self.stack)


def _rows(stack: Params, start: int, stop: int):
    """A whole stack as it is (the loop slices it), or part of one."""
    if (start, stop) == (0, jax.tree_util.tree_leaves(stack)[0].shape[0]):
        return stack
    return _Rows(stack, start, stop)


def _scanned(tree):
    """What the loop over layers takes from ``tree``, and how its body gets
    one layer back."""
    if isinstance(tree, _Rows):
        return tree.index, tree.row
    return tree, lambda layer: layer


def _save_all_but_row_reads(prim, *_, **__) -> bool:
    """Remat policy of a loop over ``_Rows``: keep every value for the
    backward pass as without remat, except a layer's weights read by index
    (a dynamic slice and its squeeze), which the backward pass reads again
    rather than keep a copy of every layer's weights."""
    return prim not in (jax.lax.dynamic_slice_p, jax.lax.squeeze_p)


def _run_layers(carry, layer_params, layer_lora, cfg: ModelConfig, *,
                positions, impl: str, remat: bool, use_lora_kernel: bool,
                unroll: bool):
    """One run of layers of one kind (``cfg`` its uniform configuration) over
    ``carry`` = (x, aux); ``layer_params``/``layer_lora`` are stacked along
    the leading axis, or ``_Rows`` of a stack."""
    from repro.shardctx import constrain

    params_xs, params_row = _scanned(layer_params)
    lora_xs, lora_row = _scanned(layer_lora)

    def body(carry, scanned):
        x, aux = carry
        if layer_lora is not None:
            lp, ll = scanned
            lp, ll = params_row(lp), lora_row(ll)
        else:
            lp, ll = params_row(scanned), None
        x = constrain(x, "dp", None, None)
        x, aux_l = blocks.layer_forward(lp, ll, x, cfg, positions=positions,
                                        impl=impl,
                                        use_lora_kernel=use_lora_kernel)
        return (x, aux + aux_l), None

    if remat or cfg.family == "ssm":
        # a Mamba layer is recomputed in the backward pass: the SSD scan's
        # and the MLP's activations of every layer, kept, do not fit one
        # chip beside the model (PERF.md)
        body = jax.checkpoint(body)
    elif isinstance(layer_params, _Rows) or isinstance(layer_lora, _Rows):
        body = jax.checkpoint(body, policy=_save_all_but_row_reads)

    scanned = (params_xs, lora_xs) if layer_lora is not None else params_xs
    if unroll:
        # python loop -> unrolled HLO: required for exact cost_analysis FLOPs
        # (XLA's HloCostAnalysis counts while-loop bodies once, ignoring the
        # trip count) — the dry-run/roofline path uses this.
        take = lambda tree, i: jax.tree_util.tree_map(lambda v: v[i], tree)
        for i in range(jax.tree_util.tree_leaves(params_xs)[0].shape[0]):
            carry, _ = body(carry, take(scanned, i))
        return carry

    carry, _ = jax.lax.scan(body, carry, scanned)
    return carry


def logits_from_hidden(frozen: Params, x: jax.Array, cfg: ModelConfig
                       ) -> jax.Array:
    x = rms_norm(x, frozen["final_norm"], cfg.rms_eps)
    head = frozen["head"] if not cfg.tie_embeddings else frozen["embed"].T
    logits = jnp.matmul(x, head.astype(x.dtype),
                        preferred_element_type=ACC_DTYPE)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab_size:
        # mask pad columns (elementwise => sharding-friendly, no gather)
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, -1e30)
    return logits


def forward_loss(frozen: Params, lora: Optional[Params], batch: Dict[str, Any],
                 cfg: ModelConfig, *, impl: str = "chunked",
                 remat: bool = True, use_lora_kernel: bool = False,
                 unroll: bool = False) -> jax.Array:
    inputs = batch["embeds"] if cfg.input_mode == "embeds" else batch["tokens"]
    x, aux = forward_hidden(frozen, lora, inputs, cfg, impl=impl, remat=remat,
                            use_lora_kernel=use_lora_kernel, unroll=unroll)
    logits = logits_from_hidden(frozen, x, cfg)
    return softmax_cross_entropy(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Per-layer caches stacked along a leading axis for lax.scan over
    layers (one stack per kind in a mixed stack)."""
    dtype = dtype_of(cfg.dtype)

    def stack(kind, n):
        one = blocks.init_layer_cache(cfg.kind_config(kind), batch, max_len,
                                      dtype)
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), one)

    if not cfg.layer_types:
        return stack(cfg.family, cfg.n_layers)
    return {kind: stack(kind, n)
            for kind, n in cfg.kind_counts(0, cfg.n_layers).items()}


def decode_step(frozen: Params, lora: Optional[Params], cache: Params,
                inputs: jax.Array, t: jax.Array, cfg: ModelConfig,
                *, unroll: bool = False, use_lora_kernel: bool = False
                ) -> Tuple[jax.Array, Params]:
    """One token for the whole stack. inputs: (B,1) tokens or (B,1,d) embeds;
    t: int32 position — scalar (lock-step batch) or (B,) vector (continuous
    batching: each row decodes at its own position). Returns
    (logits (B,vocab), new cache)."""
    x = embed_inputs(frozen, inputs, cfg)
    runs: Dict[str, List[Params]] = {}
    for kind, start, stop in layer_runs(cfg, 0, cfg.n_layers):
        rows = lambda tree: _rows(kind_stack(cfg, tree, kind), start, stop)
        x, new_c = _decode_layers(
            x, rows(frozen["layers"]),
            None if lora is None else rows(lora["layers"]),
            slice_layers(kind_stack(cfg, cache, kind), start, stop),
            cfg.kind_config(kind), t=t, unroll=unroll,
            use_lora_kernel=use_lora_kernel)
        runs.setdefault(kind, []).append(new_c)
    stacks = {kind: parts[0] if len(parts) == 1 else jax.tree_util.tree_map(
                  lambda *xs: jnp.concatenate(xs, axis=0), *parts)
              for kind, parts in runs.items()}
    new_cache = stacks if cfg.layer_types else stacks[cfg.family]
    logits = logits_from_hidden(frozen, x, cfg)
    return logits[:, 0], new_cache


def _decode_layers(x, layer_params, layer_lora, cache: Params,
                   cfg: ModelConfig, *, t, unroll: bool,
                   use_lora_kernel: bool) -> Tuple[jax.Array, Params]:
    """One token through one run of layers of one kind."""
    params_xs, params_row = _scanned(layer_params)
    lora_xs, lora_row = _scanned(layer_lora)

    def body(x, scanned):
        if layer_lora is not None:
            lp, ll, lc = scanned
            lp, ll = params_row(lp), lora_row(ll)
        else:
            (lp, lc), ll = scanned, None
            lp = params_row(lp)
        x, new_c = blocks.layer_decode(lp, ll, x, lc, cfg, t=t,
                                       use_lora_kernel=use_lora_kernel)
        return x, new_c

    scanned = ((params_xs, lora_xs, cache) if layer_lora is not None
               else (params_xs, cache))
    if unroll:
        take = lambda tree, i: jax.tree_util.tree_map(lambda v: v[i], tree)
        new_caches = []
        for i in range(jax.tree_util.tree_leaves(cache)[0].shape[0]):
            x, nc = body(x, take(scanned, i))
            new_caches.append(nc)
        return x, jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *new_caches)
    return jax.lax.scan(body, x, scanned)


def decode_scan(frozen: Params, lora: Optional[Params], cache: Params,
                tokens: jax.Array, t0: jax.Array, cfg: ModelConfig,
                *, use_lora_kernel: bool = False) -> Tuple[jax.Array, Params]:
    """Consume C tokens with C sequential ``decode_step``s in ONE jitted
    call — bit-identical to the token-by-token host loop, so it is valid
    for every family including cumulative-state SSM/hybrid. tokens:
    (B, C) int32; t0: scalar int32 position of tokens[:, 0]. Returns
    (logits after the last token (B, vocab), new cache)."""
    c = tokens.shape[1]
    t0 = jnp.asarray(t0, jnp.int32)

    def body(carry, inp):
        cache, _ = carry
        tok, i = inp
        logits, cache = decode_step(frozen, lora, cache, tok, t0 + i, cfg,
                                    use_lora_kernel=use_lora_kernel)
        return (cache, logits), None

    xs = (jnp.moveaxis(tokens, 1, 0)[:, :, None],          # (C, B, 1)
          jnp.arange(c, dtype=jnp.int32))
    zero_logits = jnp.zeros((tokens.shape[0], cfg.padded_vocab), ACC_DTYPE)
    (cache, logits), _ = jax.lax.scan(body, (cache, zero_logits), xs)
    return logits, cache


def prefill_chunk(frozen: Params, lora: Optional[Params], cache: Params,
                  tokens: jax.Array, t0: jax.Array, cfg: ModelConfig,
                  *, use_lora_kernel: bool = False
                  ) -> Tuple[jax.Array, Params]:
    """Parallel multi-token prefill against the decode cache: one forward
    over a C-token chunk that writes K/V where ``decode_step`` would have,
    position by position. tokens: (B, C) int32; t0: scalar int32 position
    of tokens[:, 0]. Returns (last-position logits (B, vocab), new cache).

    Attention families only — SSM/hybrid cumulative state cannot be
    written in parallel; use ``decode_scan`` there (exact, still one
    jitted call per chunk).
    """
    if cfg.has_ssm:
        raise ValueError(
            f"prefill_chunk does not support family={cfg.family!r} "
            "(cumulative SSM state); use decode_scan")
    x = embed_inputs(frozen, tokens, cfg)
    positions = jnp.asarray(t0, jnp.int32) + jnp.arange(tokens.shape[1],
                                                        dtype=jnp.int32)

    def body(x, scanned):
        if lora is not None:
            lp, ll, lc = scanned
        else:
            (lp, lc), ll = scanned, None
        x, new_c = blocks.layer_prefill(lp, ll, x, lc, cfg,
                                        positions=positions,
                                        use_lora_kernel=use_lora_kernel)
        return x, new_c

    scanned = ((frozen["layers"], lora["layers"], cache)
               if lora is not None else (frozen["layers"], cache))
    x, new_cache = jax.lax.scan(body, x, scanned)
    logits = logits_from_hidden(frozen, x[:, -1:], cfg)
    return logits[:, 0], new_cache


def prefill(frozen: Params, lora: Optional[Params], inputs: jax.Array,
            cfg: ModelConfig, *, impl: str = "chunked", remat: bool = False,
            unroll: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Prefill forward: returns (last-position logits, full hidden).

    Note: cache population during prefill reuses the per-layer k/v returned
    by attention; for the dry-run we lower the compute-dominant path
    (hidden + logits), matching vLLM-style chunked prefill cost.
    """
    x, _ = forward_hidden(frozen, lora, inputs, cfg, impl=impl, remat=remat,
                          unroll=unroll)
    logits = logits_from_hidden(frozen, x[:, -1:], cfg)
    return logits[:, 0], x
