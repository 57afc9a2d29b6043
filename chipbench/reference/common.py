"""Plain float32 building blocks shared by the references.

Every matmul goes through ``mm``: at ``"f32"`` it runs at "highest"
precision (a TPU otherwise rounds float32 operands to bfloat16), and at
``"fp8"`` both operands are first rounded to float8 e4m3 with one scale per
tensor, as an fp8 forward pass would, and the product accumulates in
float32. The fp8 rounding passes gradients straight through, so a control
run's backward pass is float32. Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


@jax.custom_vjp
def fp8_round(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _fp8_fwd(x):
    return fp8_round(x), None


def _fp8_bwd(_, g):
    return (g,)


fp8_round.defvjp(_fp8_fwd, _fp8_bwd)


def mm(x, w, prec: str = "f32"):
    x, w = x.astype(F32), w.astype(F32)
    if prec == "fp8":
        x, w = fp8_round(x), fp8_round(w)
    elif prec != "f32":
        raise ValueError(f"unknown precision {prec!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def einsum(spec, *ops, prec: str = "f32"):
    ops = [o.astype(F32) for o in ops]
    if prec == "fp8":
        ops = [fp8_round(o) for o in ops]
    return jnp.einsum(spec, *ops, precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def lora_linear(x, w, pair, scale, prec="f32"):
    """x @ W + scale * (x @ A) @ B, the adapted projection."""
    y = mm(x, w, prec)
    if pair is not None:
        y = y + scale * mm(mm(x, pair["a"], prec), pair["b"], prec)
    return y


def _int8_roundtrip(x):
    """Symmetric int8 per row of the last axis, as the link sends it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def int8_link(x):
    """The split link: the smashed data crosses as int8 on the way up,
    and its gradient crosses as int8 on the way down."""
    return _int8_roundtrip(x)


def _link_fwd(x):
    return _int8_roundtrip(x), None


def _link_bwd(_, g):
    return (_int8_roundtrip(g),)


int8_link.defvjp(_link_fwd, _link_bwd)


def cross_entropy(logits, labels):
    logits = logits.astype(F32)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, F32)).astype(dtype)


def lora_pair(key, d_in, d_out, rank, b_std):
    """A ~ N(0, 1/rank); B small and nonzero so every adapter leaf has a
    gradient from the first step on."""
    ka, kb = jax.random.split(key)
    return {"a": normal(ka, (d_in, rank), rank ** -0.5, F32),
            "b": normal(kb, (rank, d_out), b_std, F32)}


def key_from_seed(seed: int, tag: int):
    word = int(np.random.SeedSequence([int(seed) % 2**63, tag]
                                      ).generate_state(1)[0])
    return jax.random.PRNGKey(np.uint32(word))


# -- AdamW, as the configuration states it ----------------------------------


def adamw_init(params):
    z = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    return {"m": z, "v": z, "step": 0}


def adamw_step(params, grads, state, *, lr, b1, b2, eps, weight_decay):
    step = state["step"] + 1
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                               state["v"], grads)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    new = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                    + weight_decay * p),
        params, m, v)
    return new, {"m": m, "v": v, "step": step}
