"""Split execution — the SL computation itself, in JAX (Sec. II-B stages 3-4).

The device stage (embedding + layers [0,c) + its LoRA adapters) and the
server stage (layers [c,I) + final norm + head + loss + its adapters) are
separate jitted functions bridged by ``jax.vjp``:

  device:  smashed = f_D(lora_D; x)                        (Eq. 2)
  channel: smashed' = Q(smashed)         phi-compression (int8 quantization)
  server:  loss, d(smashed'), grads_S = f_S(lora_S; smashed', y)   (Eq. 3-4)
  channel: g' = Q(d smashed')
  device:  grads_D = vjp_D(g')                              (Eq. 5)

The compression is a straight-through int8 quantizer: the paper models phi
as a data-size ratio on the link (Eq. 9); here it is also *executed* so the
training dynamics include the quantization error.

Attention inside the stages runs the Pallas flash kernels, forward and
backward, on a TPU, and the naive path, the tests' oracle, elsewhere
(``attention_impl``).

A cut is a static argument — each cut compiles its own program (cut changes
at round granularity, Alg. 1). ``SplitExecutor.step`` runs one local epoch
as one program, ``split_grads_full``: the adapters split at the cut, both
stages, and the gradients merged back.

Inside ``split_grads`` each stage runs under a ``jax.named_scope`` (the
``SCOPE_*`` names), which reaches the compiled program's op metadata: the
backward pass carries the same scope inside ``transpose(...)``. The host
side of a step and of a device's round opens ``jax.profiler`` spans (the
``SPAN_*`` names). With the profiler off a scope costs nothing and a span
next to nothing; a profiler trace reads them to put device time and host
gaps down to a stage.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.models.common import Params, softmax_cross_entropy


# ---------------------------------------------------------------------------
# Trace names of the split path
# ---------------------------------------------------------------------------

# named scopes inside split_grads, disjoint
SCOPE_DEVICE_STAGE = "sl.device_stage"    # embedding + layers [0, c)
SCOPE_LINK = "sl.link"                    # int8 round trip, both ways
SCOPE_SERVER_LAYERS = "sl.server_layers"  # layers [c, I)
SCOPE_HEAD = "sl.head"                    # final norm + head + loss
SCOPES = (SCOPE_DEVICE_STAGE, SCOPE_LINK, SCOPE_SERVER_LAYERS, SCOPE_HEAD)

# host spans; all but SPAN_ROUND nest in it
SPAN_ROUND = "sl.round"            # SplitFineTuner.run_round
SPAN_DECIDE = "sl.decide"          # channel draw + cost context + policy
SPAN_BATCH = "sl.batch"            # one local epoch's minibatch
SPAN_DISPATCH = "sl.dispatch"      # the split_grads_full call
SPAN_OPTIMIZER = "sl.optimizer"    # the compiled optimizer update + apply
SPAN_LOSS_SYNC = "sl.loss_sync"    # the round's one wait for the device
SPANS = (SPAN_ROUND, SPAN_DECIDE, SPAN_BATCH, SPAN_DISPATCH, SPAN_OPTIMIZER,
         SPAN_LOSS_SYNC)


# ---------------------------------------------------------------------------
# Channel compression (phi)
# ---------------------------------------------------------------------------


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-last-axis int8 quantization of smashed activations:
    returns (int8 values shaped like ``x``, float32 scales with the last
    axis kept as size 1)."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def channel_compress(x: jax.Array, enabled: bool) -> jax.Array:
    """Straight-through int8 round trip emulating the phi-compressed link."""
    if not enabled:
        return x
    with jax.named_scope(SCOPE_LINK):
        q, s = quantize_int8(x)
        xq = dequantize_int8(q, s, x.dtype)
        return x + jax.lax.stop_gradient(xq - x)


# ---------------------------------------------------------------------------
# Stage functions
# ---------------------------------------------------------------------------


def split_lora(lora: Params, cut: int, cfg: Optional[ModelConfig] = None
               ) -> Tuple[Params, Params]:
    """R = {R^D ; R^S} (Eq. 6, inverse): split adapters at the cut. A
    mixed stack (``cfg.layer_types``) splits each kind's stack at the rows
    of the layers below the cut."""
    if cfg is not None and cfg.layer_types:
        return ({"layers": model_lib.slice_stack(cfg, lora["layers"], 0, cut)},
                {"layers": model_lib.slice_stack(cfg, lora["layers"], cut,
                                                 cfg.n_layers)})
    dev = {"layers": model_lib.slice_layers(lora["layers"], 0, cut)}
    n = jax.tree_util.tree_leaves(lora["layers"])[0].shape[0]
    srv = {"layers": model_lib.slice_layers(lora["layers"], cut, n)}
    return dev, srv


def merge_lora(dev: Params, srv: Params) -> Params:
    """Stage 5, Eq. 6: R = {R^{D,T} ; R^{S,T}}; each leaf's rows, per kind
    in a mixed stack, are the device's then the server's."""
    merged = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0),
        dev["layers"], srv["layers"])
    return {"layers": merged}


def attention_impl(impl: Optional[str] = None) -> str:
    """The split step's attention path: ``impl`` if given, else the Pallas
    flash kernels (forward and backward) on a TPU and the naive oracle
    elsewhere, where the kernels would only run interpreted."""
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "naive"


def device_forward(frozen: Params, lora_dev: Params, inputs: jax.Array,
                   cfg: ModelConfig, cut: int, *, impl: Optional[str] = None,
                   compress: bool = True) -> jax.Array:
    """Eq. 2: smashed data at the cut layer (embedding + layers [0,c))."""
    with jax.named_scope(SCOPE_DEVICE_STAGE):
        if cut == 0:
            x = model_lib.embed_inputs(frozen, inputs, cfg)
        else:
            lora_full = {"layers": lora_dev["layers"]}
            x, _ = model_lib.forward_hidden(
                frozen, lora_full, inputs, cfg, lo=0, hi=cut,
                impl=attention_impl(impl), remat=False, lora_sliced=True)
    return channel_compress(x, compress)


def server_loss(frozen: Params, lora_srv: Params, smashed: jax.Array,
                labels: jax.Array, cfg: ModelConfig, cut: int, *,
                impl: Optional[str] = None) -> jax.Array:
    """Eq. 3 + loss: layers [c,I) + final norm + head + CE."""
    if cut == cfg.n_layers:
        x, aux = smashed, 0.0
    else:
        lora_full = {"layers": lora_srv["layers"]}
        with jax.named_scope(SCOPE_SERVER_LAYERS):
            x, aux = model_lib.forward_hidden(
                frozen, lora_full, smashed, cfg, lo=cut, hi=cfg.n_layers,
                impl=attention_impl(impl), remat=False, inputs_embedded=True,
                lora_sliced=True)
    with jax.named_scope(SCOPE_HEAD):
        logits = model_lib.logits_from_hidden(frozen, x, cfg)
        return softmax_cross_entropy(logits, labels) + aux


# ---------------------------------------------------------------------------
# One split fine-tuning step (stages 3-4)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "cut", "impl", "compress"))
def split_grads(frozen: Params, lora_dev: Params, lora_srv: Params,
                inputs: jax.Array, labels: jax.Array, *, cfg: ModelConfig,
                cut: int, impl: Optional[str] = None, compress: bool = True
                ) -> Tuple[jax.Array, Params, Params]:
    """Returns (loss, grads_dev, grads_srv) with the smashed-data gradient
    crossing the (compressed) channel boundary — exactly stages 3-4."""
    # --- device-side FP, with vjp captured for the later BP ----------------
    def dev_fn(ld):
        return device_forward(frozen, ld, inputs, cfg, cut, impl=impl,
                              compress=compress)

    smashed, dev_vjp = jax.vjp(dev_fn, lora_dev)

    # --- uplink: smashed data + labels (compression already applied) -------
    # --- server-side FP + BP ------------------------------------------------
    def srv_fn(ls, sm):
        return server_loss(frozen, ls, sm, labels, cfg, cut, impl=impl)

    loss, srv_vjp = jax.vjp(srv_fn, lora_srv, smashed)
    grads_srv, g_smashed = srv_vjp(jnp.ones((), loss.dtype))

    # --- downlink: smashed-data gradient, phi-compressed --------------------
    g_smashed = channel_compress(g_smashed, compress)

    # --- device-side BP ------------------------------------------------------
    (grads_dev,) = dev_vjp(g_smashed)
    return loss, grads_dev, grads_srv


class SplitExecutor:
    """Runs the split step as one compiled program per cut (Stage 1
    re-splits per round); the program is named ``jit_split_grads_full``."""

    def __init__(self, cfg: ModelConfig, *, impl: Optional[str] = None,
                 compress: bool = True):
        self.cfg = cfg
        impl = attention_impl(impl)

        def split_grads_full(frozen: Params, lora: Params, inputs: jax.Array,
                             labels: jax.Array, *, cut: int
                             ) -> Tuple[jax.Array, Params]:
            """The adapters split at the cut, ``split_grads`` (inlined), the
            gradients merged back."""
            lora_dev, lora_srv = split_lora(lora, cut, cfg)
            loss, g_dev, g_srv = split_grads(
                frozen, lora_dev, lora_srv, inputs, labels, cfg=cfg, cut=cut,
                impl=impl, compress=compress)
            return loss, merge_lora(g_dev, g_srv)

        # this executor's own program, traced from the stage functions as
        # they stand when it first runs at a cut; no donation, since callers
        # keep the adapters they pass
        self.compiled_step = jax.jit(split_grads_full, static_argnames="cut")

    def step(self, frozen: Params, lora: Params, batch: Dict[str, Any],
             cut: int) -> Tuple[jax.Array, Params]:
        """One local epoch: returns (loss, full-model LoRA grads)."""
        inputs = (batch["embeds"] if self.cfg.input_mode == "embeds"
                  else batch["tokens"])
        with TraceAnnotation(SPAN_DISPATCH):
            return self.compiled_step(frozen, lora, inputs, batch["labels"],
                                      cut=cut)
