"""Plain float32 reference of a dense GQA decoder with LoRA adapters
(Qwen3: RMSNorm, per-head q/k RMSNorm, rotate-half RoPE, causal softmax
attention, SwiGLU MLP, tied embeddings), and the work it requires.

The configuration is the JSON dict of ``configs/<name>.json``. The weights
are made here from the seed, in the layout the program reads: frozen
weights in bfloat16 (as served), stacked over layers; adapters in float32.
The forward pass, the loss and its gradients are written from the published
architecture, in float32 at "highest" precision, with the split link's int8
round trip at the cut.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.reference.common import (F32, cross_entropy, einsum,
                                        int8_link, lora_linear, lora_pair, mm,
                                        normal, rms_norm, silu)

BF16 = jnp.bfloat16
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
B_STD = 0.002

# configuration key -> the program's ModelConfig field it must equal
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "qk_norm": "qk_norm", "torch_dtype": "dtype",
}


def sizes(c: Dict) -> Dict[str, int]:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    f = c["intermediate_size"]
    return {"d": d, "q": q, "kv": kv, "f": f, "hd": hd,
            "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "Vp": -(-c["vocab_size"] // 256) * 256,
            "r": c["lora"]["rank"]}


def _shapes(c: Dict) -> Dict[str, tuple]:
    s = sizes(c)
    d, q, kv, f = s["d"], s["q"], s["kv"], s["f"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def make_frozen(c: Dict, key) -> Dict:
    """Frozen weights from ``key`` in one traced call."""
    s = sizes(c)
    shapes = _shapes(c)
    keys = iter(jax.random.split(key, 16))
    L = s["L"]

    def dense(name):
        d_in, d_out = shapes[name]
        return normal(next(keys), (L, d_in, d_out), d_in ** -0.5, BF16)

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)

    layers = {"norm1": norm((L, s["d"])), "norm2": norm((L, s["d"])),
              "attn": {n: dense(n) for n in ATTN},
              "mlp": {n: dense(n) for n in MLP}}
    if c.get("qk_norm"):
        layers["attn"]["q_norm"] = norm((L, s["hd"]))
        layers["attn"]["k_norm"] = norm((L, s["hd"]))
    return {"embed": normal(next(keys), (s["Vp"], s["d"]), 0.02, BF16),
            "layers": layers, "final_norm": norm((s["d"],))}


def make_lora(c: Dict, key) -> Dict:
    s = sizes(c)
    shapes = _shapes(c)
    targets = c["lora"]["targets"]
    keys = jax.random.split(key, len(targets))
    out = {"attn": {}, "mlp": {}}
    for k, name in zip(keys, targets, strict=True):
        d_in, d_out = shapes[name]
        pairs = jax.vmap(lambda kk, a=d_in, b=d_out: lora_pair(
            kk, a, b, s["r"], B_STD))(jax.random.split(k, s["L"]))
        out["attn" if name in ATTN else "mlp"][name] = pairs
    return {"layers": out}


def _rope(x, positions, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[..., :, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(c: Dict, p: Dict, lo: Optional[Dict], x, positions, prec="f32"):
    """One decoder layer over (B, S, d); causal within the sequence."""
    s = sizes(c)
    scale = c["lora"]["alpha"] / c["lora"]["rank"]
    eps = c["rms_norm_eps"]
    la = (lo or {}).get("attn", {})
    lm = (lo or {}).get("mlp", {})
    b, n, _ = x.shape
    h = rms_norm(x, p["norm1"], eps)
    q = lora_linear(h, p["attn"]["wq"], la.get("wq"), scale, prec)
    k = lora_linear(h, p["attn"]["wk"], la.get("wk"), scale, prec)
    v = lora_linear(h, p["attn"]["wv"], la.get("wv"), scale, prec)
    q = q.reshape(b, n, s["hq"], s["hd"])
    k = k.reshape(b, n, s["hkv"], s["hd"])
    v = v.reshape(b, n, s["hkv"], s["hd"])
    if c.get("qk_norm"):
        q = rms_norm(q, p["attn"]["q_norm"], eps)
        k = rms_norm(k, p["attn"]["k_norm"], eps)
    q = _rope(q, positions, c["rope_theta"])
    k = _rope(k, positions, c["rope_theta"])
    g = s["hq"] // s["hkv"]
    qg = q.reshape(b, n, s["hkv"], g, s["hd"])
    scores = einsum("bqhgd,bkhd->bhgqk", qg, k, prec=prec) / jnp.sqrt(
        float(s["hd"]))
    causal = positions[:, None, :] <= positions[:, :, None]      # (b, q, k)
    scores = jnp.where(causal[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    o = einsum("bhgqk,bkhd->bqhgd", probs, v, prec=prec).reshape(
        b, n, s["q"])
    x = x + lora_linear(o, p["attn"]["wo"], la.get("wo"), scale, prec)
    h = rms_norm(x, p["norm2"], eps)
    gate = lora_linear(h, p["mlp"]["w_gate"], lm.get("w_gate"), scale, prec)
    up = lora_linear(h, p["mlp"]["w_up"], lm.get("w_up"), scale, prec)
    return x + lora_linear(silu(gate) * up, p["mlp"]["w_down"],
                           lm.get("w_down"), scale, prec)


def _stack(c, frozen, lora, x, positions, lo, hi, prec):
    """Layers [lo, hi) as a scan, each layer recomputed in the backward
    pass so that the reference fits beside nothing else."""
    if hi <= lo:
        return x
    sl = lambda t: jax.tree_util.tree_map(lambda v: v[lo:hi], t)
    xs = (sl(frozen["layers"]), None if lora is None else sl(lora["layers"]))

    @jax.checkpoint
    def body(h, pl):
        return layer(c, pl[0], pl[1], h, positions, prec), None

    return jax.lax.scan(body, x, xs)[0]


def hidden(c, frozen, lora, tokens, *, cut: Optional[int] = None,
           prec="f32"):
    """Final-normed hidden states; the int8 link sits before layer ``cut``
    (``None``: no link)."""
    x = frozen["embed"][tokens].astype(F32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    n = sizes(c)["L"]
    if cut is None:
        x = _stack(c, frozen, lora, x, positions, 0, n, prec)
    else:
        x = _stack(c, frozen, lora, x, positions, 0, cut, prec)
        x = int8_link(x)
        x = _stack(c, frozen, lora, x, positions, cut, n, prec)
    return rms_norm(x, frozen["final_norm"], c["rms_norm_eps"])


def logits(c, frozen, x, prec="f32"):
    """Tied head over the real vocabulary."""
    head = frozen["embed"][: sizes(c)["V"]]
    return mm(x, head.T, prec)


def split_loss(c, frozen, lora, tokens, labels, cut, prec="f32"):
    x = hidden(c, frozen, lora, tokens, cut=cut, prec=prec)
    return cross_entropy(logits(c, frozen, x, prec), labels)


# -- the work the algorithm requires -----------------------------------------


def _proj_flops(c: Dict) -> Dict[str, int]:
    return {n: 2 * a * b for n, (a, b) in _shapes(c).items()}


def _lora_flops(c: Dict) -> int:
    r = sizes(c)["r"]
    return sum(2 * r * (a + b) for n, (a, b) in _shapes(c).items()
               if n in c["lora"]["targets"])


def _attn_flops(c: Dict, keys: float) -> float:
    """QK^T and PV for one query over ``keys`` keys."""
    s = sizes(c)
    return 2 * 2 * s["hq"] * s["hd"] * keys


def train_flops(c: Dict, batch: int, seq: int, cut: int) -> float:
    """One LoRA step: forward, activation gradients through the frozen
    weights, and the adapters' own gradients; attention causal; no
    recomputation; no gradient into the first layer's input, which
    nothing needs."""
    s = sizes(c)
    proj = sum(_proj_flops(c).values())
    lora = _lora_flops(c)
    attn = _attn_flops(c, (seq + 1) / 2.0)
    per_layer = 2 * proj + 3 * lora + 3 * attn
    first_input = sum(v for n, v in _proj_flops(c).items()
                      if n in ("wq", "wk", "wv"))
    first_input += sum(2 * s["r"] * s["d"] for n in ("wq", "wk", "wv")
                       if n in c["lora"]["targets"])
    head = 2 * 2 * s["d"] * s["V"]
    del cut
    return batch * seq * (s["L"] * per_layer - first_input + head)

