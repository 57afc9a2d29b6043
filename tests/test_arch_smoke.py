"""Per-architecture smoke tests: reduced variant of each family, one
forward/train step + one decode step on CPU; asserts shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models import model as M
from repro.optim import adamw, constant_schedule, apply_updates


def _batch(cfg, key, b=2, s=32):
    k_tok, k_emb = jax.random.split(key)
    toks = jax.random.randint(k_tok, (b, s + 1), 0, cfg.vocab_size)
    batch = {"labels": toks[:, 1:]}
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(k_emb, (b, s, cfg.d_model),
                                            jnp.float32) * 0.02
    else:
        batch["tokens"] = toks[:, :-1]
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    batch = _batch(cfg, key)

    # forward
    loss = M.forward_loss(params["frozen"], params["lora"], batch, cfg,
                          impl="naive", remat=False)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    # one LoRA train step
    opt = adamw(constant_schedule(1e-3))
    state = opt.init(params["lora"])

    def lf(lora):
        return M.forward_loss(params["frozen"], lora, batch, cfg,
                              impl="naive", remat=False)

    loss0, grads = jax.value_and_grad(lf)(params["lora"])
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree_util.tree_leaves(grads)) ** 0.5
    assert gnorm > 0, f"{arch}: zero LoRA gradient"
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in jax.tree_util.tree_leaves(grads))
    upd, state = opt.update(grads, state, params["lora"])
    lora2 = apply_updates(params["lora"], upd)
    loss1 = lf(lora2)
    assert bool(jnp.isfinite(loss1))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    k_param, k_inp = jax.random.split(key)
    params = M.init_params(k_param, cfg)
    b, max_len = 2, 16
    cache = M.init_cache(cfg, b, max_len)
    if cfg.input_mode == "embeds":
        inp = jax.random.normal(k_inp, (b, 1, cfg.d_model), jnp.float32)
    else:
        inp = jax.random.randint(k_inp, (b, 1), 0, cfg.vocab_size)
    logits, cache2 = M.decode_step(params["frozen"], params["lora"], cache,
                                   inp, jnp.int32(0), cfg)
    assert logits.shape == (b, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # cache structure preserved
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(cache2)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-370m", "hymba-1.5b",
                                  "granite-moe-3b-a800m", "musicgen-large",
                                  "granite-4.0-h-micro"])
def test_decode_matches_forward(arch):
    """Step-by-step decode must reproduce the full-sequence forward."""
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(2)
    k_param, k_inp = jax.random.split(key)
    params = M.init_params(k_param, cfg)
    b, s = 2, 12
    if cfg.input_mode == "embeds":
        inputs = jax.random.normal(k_inp, (b, s, cfg.d_model),
                                   jnp.float32) * 0.1
        step_in = lambda t: inputs[:, t:t + 1]
    else:
        inputs = jax.random.randint(k_inp, (b, s), 0, cfg.vocab_size)
        step_in = lambda t: inputs[:, t:t + 1]
    x, _ = M.forward_hidden(params["frozen"], params["lora"], inputs, cfg,
                            impl="naive", remat=False)
    full = M.logits_from_hidden(params["frozen"], x, cfg)
    cache = M.init_cache(cfg, b, s)
    outs = []
    for t in range(s):
        lg, cache = M.decode_step(params["frozen"], params["lora"], cache,
                                  step_in(t), jnp.int32(t), cfg)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 5e-4


def test_sliding_window_ring_buffer_decode():
    """SWA ring-buffer cache must equal full-cache attention within window."""
    from dataclasses import replace
    cfg = replace(get_config("qwen3-0.6b").reduced(), sliding_window=8)
    key = jax.random.PRNGKey(3)
    params = M.init_params(key, cfg)
    b, s = 1, 20
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    x, _ = M.forward_hidden(params["frozen"], params["lora"], toks, cfg,
                            impl="naive", remat=False)
    full = M.logits_from_hidden(params["frozen"], x, cfg)
    cache = M.init_cache(cfg, b, s)   # ring: 8 slots only
    assert cache["kv"]["k"].shape[2] == 8
    outs = []
    for t in range(s):
        lg, cache = M.decode_step(params["frozen"], params["lora"], cache,
                                  toks[:, t:t + 1], jnp.int32(t), cfg)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 5e-4


def test_int8_kv_cache_decode():
    """int8 KV cache (phi-compression applied to serving) stays within
    quantization tolerance of the fp cache decode."""
    from dataclasses import replace
    cfg = replace(get_config("qwen3-0.6b").reduced(), kv_cache_dtype="int8")
    key = jax.random.PRNGKey(4)
    params = M.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    x, _ = M.forward_hidden(params["frozen"], params["lora"], toks, cfg,
                            impl="naive", remat=False)
    full = M.logits_from_hidden(params["frozen"], x, cfg)
    cache = M.init_cache(cfg, 2, 16)
    assert cache["kv"]["k"].dtype == jnp.int8
    assert "k_scale" in cache["kv"]
    outs = []
    for t in range(16):
        lg, cache = M.decode_step(params["frozen"], params["lora"], cache,
                                  toks[:, t:t + 1], jnp.int32(t), cfg)
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 0.15
