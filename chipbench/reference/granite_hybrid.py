"""Plain float32 reference of Granite 4.0-H (``granitemoehybrid`` without
experts) with LoRA adapters, and the work it requires.

The published layer stack: each layer's kind is ``layer_types[i]``, a
Mamba-2 mixer or GQA attention without positions (NoPE) and softmax scale
``attention_multiplier``; every mixer is followed by a SwiGLU MLP of width
``shared_intermediate_size``; both block outputs join the residual stream
times ``residual_multiplier``. Embeddings are scaled by
``embedding_multiplier`` and logits divided by ``logits_scaling``; the head
is tied; every norm is RMSNorm.

The Mamba-2 mixer (one B/C group): in_proj gives z, x, B, C and one dt per
head; x, B, C pass a depthwise causal conv with bias and SiLU;
dt = softplus(dt + dt_bias), A = -exp(A_log); the SSM is written whole
over the sequence in its masked (semiseparable) form,
y = (L o C B^T) (dt x) + D x with L[i, j] = exp(sum_{j<k<=i} dt_k A) for
j <= i and 0 above, not chunked as the program computes it; then
RMSNorm(y * SiLU(z)) over the full inner width, and out_proj.

The configuration is the JSON dict of ``configs/<name>.json``. The weights
are made here from the seed, in the layout the program reads: one stack per
layer kind (``layers["mamba"]``, ``layers["attention"]``), frozen weights
in bfloat16 (as served), norms and the SSM's A_log, dt_bias and D in
float32, adapters in float32. A, dt_bias and D are drawn as Mamba-2
initializes them: A uniform in [1, 16], dt log-uniform in [0.001, 0.1]
(dt_bias its inverse softplus), D one. The forward pass, the loss and its
gradients are float32 at "highest" precision, with the split link's int8
round trip at the cut.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

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
    "shared_intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta", "attention_bias": "qkv_bias",
    "position_embedding_type": "position_embedding",
    "attention_multiplier": "attention_multiplier",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "layer_types": "layer_types",
    "mamba_chunk_size": "ssm_chunk", "mamba_d_conv": "ssm_conv_width",
    "mamba_d_head": "ssm_head_dim", "mamba_d_state": "ssm_state",
    "mamba_expand": "ssm_expand", "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k", "torch_dtype": "dtype",
}

# what this reference (and the program) implements of the family
_REQUIRED = {"hidden_act": "silu", "normalization_function": "rmsnorm",
             "mamba_n_groups": 1, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "attention_bias": False,
             "num_local_experts": 0, "position_embedding_type": "nope",
             "tie_word_embeddings": True}


def sizes(c: Dict) -> Dict[str, int]:
    for k, v in _REQUIRED.items():
        if c[k] != v:
            raise ValueError(f"{k} = {c[k]!r}; this reference takes {v!r}")
    d = c["hidden_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // hq
    di = c["mamba_expand"] * d
    nh, hp, ns = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    if nh * hp != di:
        raise ValueError(f"{nh} Mamba heads of {hp} do not span {di}")
    return {"d": d, "hq": hq, "hkv": hkv, "hd": hd, "q": hq * hd,
            "kv": hkv * hd, "f": c["shared_intermediate_size"], "di": di,
            "nh": nh, "hp": hp, "ns": ns, "conv": di + 2 * ns,
            "P": 2 * di + 2 * ns + nh, "W": c["mamba_d_conv"],
            "Q": c["mamba_chunk_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"], "Vp": -(-c["vocab_size"] // 256) * 256,
            "r": c["lora"]["rank"]}


def counts(c: Dict) -> Dict[str, int]:
    """Layers of each kind."""
    out: Dict[str, int] = {}
    for kind in c["layer_types"]:
        out[kind] = out.get(kind, 0) + 1
    return out


def _shapes(c: Dict, kind: str) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of each frozen projection of a layer of ``kind``."""
    s = sizes(c)
    d, f = s["d"], s["f"]
    mlp = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if kind == "attention":
        return {"wq": (d, s["q"]), "wk": (d, s["kv"]), "wv": (d, s["kv"]),
                "wo": (s["q"], d), **mlp}
    return {"in_proj": (d, s["P"]), "out_proj": (s["di"], d), **mlp}


def _group(name: str) -> str:
    return "attn" if name in ATTN else "mlp" if name in MLP else "mamba"


def make_frozen(c: Dict, key) -> Dict:
    """Frozen weights from ``key`` in one traced call."""
    s = sizes(c)
    keys = iter(jax.random.split(key, 64))

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    layers = {}
    for kind, n in counts(c).items():
        p = {"norm1": norm((n, s["d"])), "norm2": norm((n, s["d"]))}
        for name, (d_in, d_out) in _shapes(c, kind).items():
            p.setdefault(_group(name), {})[name] = normal(
                next(keys), (n, d_in, d_out), d_in ** -0.5, BF16)
        if kind == "mamba":
            bound = s["W"] ** -0.5   # torch's Conv1d default, fan-in W
            dt = jnp.exp(uniform((n, s["nh"]), math.log(1e-3),
                                 math.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            p["mamba"].update(
                conv_w=uniform((n, s["W"], s["conv"]), -bound, bound
                               ).astype(BF16),
                conv_b=uniform((n, s["conv"]), -bound, bound).astype(BF16),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.log(uniform((n, s["nh"]), 1.0, 16.0)),
                d_skip=jnp.ones((n, s["nh"]), F32),
                gate_norm=norm((n, s["di"])))
        layers[kind] = p
    return {"embed": normal(next(keys), (s["Vp"], s["d"]), 0.02, BF16),
            "layers": layers, "final_norm": norm((s["d"],))}


def make_lora(c: Dict, key) -> Dict:
    """Adapters on every target a layer of each kind has."""
    s = sizes(c)
    out = {}
    for i, (kind, n) in enumerate(counts(c).items()):
        shapes = _shapes(c, kind)
        names = [t for t in c["lora"]["targets"] if t in shapes]
        tree: Dict[str, Dict] = {}
        for k, name in zip(jax.random.split(jax.random.fold_in(key, i),
                                            len(names)), names, strict=True):
            d_in, d_out = shapes[name]
            tree.setdefault(_group(name), {})[name] = jax.vmap(
                lambda kk, a=d_in, b=d_out: lora_pair(kk, a, b, s["r"],
                                                      B_STD))(
                jax.random.split(k, n))
        out[kind] = tree
    return {"layers": out}


# -- the forward pass ----------------------------------------------------------


def _mlp(c, p, lo, x, prec):
    scale = c["lora"]["alpha"] / c["lora"]["rank"]
    lm = (lo or {}).get("mlp", {})
    gate = lora_linear(x, p["mlp"]["w_gate"], lm.get("w_gate"), scale, prec)
    up = lora_linear(x, p["mlp"]["w_up"], lm.get("w_up"), scale, prec)
    return lora_linear(silu(gate) * up, p["mlp"]["w_down"], lm.get("w_down"),
                       scale, prec)


def _attention(c, p, lo, x, prec):
    """Causal GQA attention without positions, softmax scale
    ``attention_multiplier``."""
    s = sizes(c)
    scale = c["lora"]["alpha"] / c["lora"]["rank"]
    la = (lo or {}).get("attn", {})
    b, n, _ = x.shape
    q = lora_linear(x, p["attn"]["wq"], la.get("wq"), scale, prec)
    k = lora_linear(x, p["attn"]["wk"], la.get("wk"), scale, prec)
    v = lora_linear(x, p["attn"]["wv"], la.get("wv"), scale, prec)
    g = s["hq"] // s["hkv"]
    qg = q.reshape(b, n, s["hkv"], g, s["hd"])
    k = k.reshape(b, n, s["hkv"], s["hd"])
    v = v.reshape(b, n, s["hkv"], s["hd"])
    scores = einsum("bqhgd,bkhd->bhgqk", qg, k, prec=prec) \
        * c["attention_multiplier"]
    causal = jnp.tril(jnp.ones((n, n), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v,
               prec=prec).reshape(b, n, s["q"])
    return lora_linear(o, p["attn"]["wo"], la.get("wo"), scale, prec)


def _mamba(c, p, lo, x, prec):
    """The Mamba-2 mixer over the whole sequence, the SSM in masked form."""
    s = sizes(c)
    scale = c["lora"]["alpha"] / c["lora"]["rank"]
    pm, lm = p["mamba"], (lo or {}).get("mamba", {})
    b, n, _ = x.shape
    di, ns, nh, hp = s["di"], s["ns"], s["nh"], s["hp"]
    proj = lora_linear(x, pm["in_proj"], lm.get("in_proj"), scale, prec)
    z, xbc, dt = proj[..., :di], proj[..., di:di + s["conv"]], \
        proj[..., di + s["conv"]:]
    w = pm["conv_w"].astype(F32)                       # (W, conv)
    padded = jnp.pad(xbc, ((0, 0), (s["W"] - 1, 0), (0, 0)))
    xbc = silu(sum(padded[:, i:i + n] * w[i] for i in range(s["W"]))
               + pm["conv_b"].astype(F32))
    xs = xbc[..., :di].reshape(b, n, nh, hp)
    B, C = xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt + pm["dt_bias"])           # (b, n, nh)
    cum = jnp.cumsum(dt * -jnp.exp(pm["a_log"]), axis=1)
    causal = jnp.tril(jnp.ones((n, n), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, cum[:, :, None] - cum[:, None], -jnp.inf))
    mixed = decay * einsum("bin,bjn->bij", C, B, prec=prec)[..., None]
    y = einsum("bijh,bjhp->bihp", mixed, xs * dt[..., None], prec=prec) \
        + pm["d_skip"][:, None] * xs
    y = rms_norm(y.reshape(b, n, di) * silu(z), pm["gate_norm"],
                 c["rms_norm_eps"])
    return lora_linear(y, pm["out_proj"], lm.get("out_proj"), scale, prec)


def layer(c: Dict, kind: str, p: Dict, lo: Optional[Dict], x, prec="f32"):
    """One layer of ``kind`` over (B, S, d); causal within the sequence."""
    eps, res = c["rms_norm_eps"], c["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    x = x + res * mixer(c, p, lo, rms_norm(x, p["norm1"], eps), prec)
    return x + res * _mlp(c, p, lo, rms_norm(x, p["norm2"], eps), prec)


def _runs(c: Dict, lo: int, hi: int) -> List[Tuple[str, int, int]]:
    """Layers [lo, hi) as runs of one kind: (kind, first row, last row + 1)
    in that kind's stack."""
    runs: List[Tuple[str, int, int]] = []
    types = c["layer_types"]
    for i in range(lo, hi):
        row = types[:i].count(types[i])
        if runs and runs[-1][0] == types[i]:
            runs[-1] = (types[i], runs[-1][1], row + 1)
        else:
            runs.append((types[i], row, row + 1))
    return runs


def _stack(c, frozen, lora, x, lo, hi, prec):
    """Layers [lo, hi), each run of one kind as a scan, each layer
    recomputed in the backward pass so that the reference fits beside
    nothing else."""
    for kind, a, b in _runs(c, lo, hi):
        sl = lambda t, a=a, b=b: jax.tree_util.tree_map(lambda v: v[a:b], t)
        xs = (sl(frozen["layers"][kind]),
              None if lora is None else sl(lora["layers"][kind]))

        @jax.checkpoint
        def body(h, pl, kind=kind):
            return layer(c, kind, pl[0], pl[1], h, prec), None

        x = jax.lax.scan(body, x, xs)[0]
    return x


def hidden(c, frozen, lora, tokens, *, cut: Optional[int] = None,
           prec="f32"):
    """Final-normed hidden states; the int8 link sits before layer ``cut``
    (``None``: no link)."""
    x = frozen["embed"][tokens].astype(F32) * c["embedding_multiplier"]
    n = c["num_hidden_layers"]
    if cut is None:
        x = _stack(c, frozen, lora, x, 0, n, prec)
    else:
        x = _stack(c, frozen, lora, x, 0, cut, prec)
        x = int8_link(x)
        x = _stack(c, frozen, lora, x, cut, n, prec)
    return rms_norm(x, frozen["final_norm"], c["rms_norm_eps"])


def logits(c, frozen, x, prec="f32"):
    """Tied head over the real vocabulary, divided by ``logits_scaling``."""
    head = frozen["embed"][: sizes(c)["V"]]
    return mm(x, head.T, prec) / c["logits_scaling"]


def split_loss(c, frozen, lora, tokens, labels, cut, prec="f32"):
    x = hidden(c, frozen, lora, tokens, cut=cut, prec=prec)
    return cross_entropy(logits(c, frozen, x, prec), labels)


# -- the work the algorithm requires -----------------------------------------


def _ssd_flops(c: Dict) -> float:
    """One token's SSD scan, chunked as published (``mamba_chunk_size``):
    C.B over the causal half of its chunk, those weights times x, its
    share of the chunk's state B^T x, and C times the state entering the
    chunk."""
    s = sizes(c)
    keys = (s["Q"] + 1) / 2.0
    return 2 * keys * (s["ns"] + s["nh"] * s["hp"]) \
        + 2 * 2 * s["ns"] * s["nh"] * s["hp"]


def layer_flops(c: Dict, kind: str, seq: int) -> float:
    """One token through one layer of ``kind``: forward and activation
    gradients of the frozen projections and the conv (2x); forward and
    both operands' gradients of the adapters, attention and the SSD scan
    (3x)."""
    s = sizes(c)
    shapes = _shapes(c, kind)
    proj = sum(2 * a * b for a, b in shapes.values())
    lora = sum(2 * s["r"] * (a + b) for n, (a, b) in shapes.items()
               if n in c["lora"]["targets"])
    if kind == "attention":
        mixing, linear = 2 * 2 * s["hq"] * s["hd"] * (seq + 1) / 2.0, 0
    else:
        mixing, linear = _ssd_flops(c), 2 * s["W"] * s["conv"]
    return 2 * (proj + linear) + 3 * (lora + mixing)


def train_flops(c: Dict, batch: int, seq: int, cut: int) -> float:
    """One LoRA step, by layer kind; no recomputation; no gradient into the
    first layer's input, which nothing needs."""
    s = sizes(c)
    first = c["layer_types"][0]
    inputs = ("wq", "wk", "wv") if first == "attention" else ("in_proj",)
    first_input = sum(2 * a * b + 2 * s["r"] * a * (n in c["lora"]["targets"])
                      for n, (a, b) in _shapes(c, first).items()
                      if n in inputs)
    head = 2 * 2 * s["d"] * s["V"]
    del cut
    return batch * seq * (sum(layer_flops(c, t, seq)
                              for t in c["layer_types"])
                          - first_input + head)
