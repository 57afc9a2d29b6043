"""Split execution (Sec. II-B stages 3-4): the SL computation itself."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.splitting import (SplitExecutor, channel_compress,
                                  dequantize_int8, device_forward, merge_lora,
                                  quantize_int8, split_grads, split_lora)
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("llama32-1b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(5)
    tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(6), (4, 32), 0,
                                cfg.vocab_size)
    return cfg, params, tokens, labels


# four layers each, so that cut 2 leaves attention on both sides: qwen3
# (qk-norm, GQA, RoPE) and a Granite-like mixed stack (Mamba-2 and NoPE
# attention at the published softmax scale 1/64)
TINY = {
    "qwen3": dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                                 n_layers=4),
    "granite": dataclasses.replace(
        get_config("granite-4.0-h-micro").reduced(), n_layers=4,
        layer_types=("mamba", "attention") * 2),
}


@pytest.mark.parametrize("cut", [0, 2])
@pytest.mark.parametrize("name", sorted(TINY))
def test_split_grads_flash_matches_naive(name, cut):
    """The split step on the flash kernels (interpreted here, as a TPU
    compiles them) gives the naive path's loss and every adapter gradient.
    The adapters are random, B included, so that no gradient is zero; the
    sequence, 40, is not a multiple of the kernels' block."""
    cfg = TINY[name]
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params["lora"])
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    lora = jax.tree_util.tree_unflatten(tree, [
        0.1 * jax.random.normal(key, a.shape, a.dtype)
        for key, a in zip(keys, leaves, strict=True)])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 0,
                                cfg.vocab_size)
    ld, ls = split_lora(lora, cut, cfg)
    loss, *grads = split_grads(params["frozen"], ld, ls, tokens[:, :-1],
                               tokens[:, 1:], cfg=cfg, cut=cut,
                               impl="pallas", compress=False)
    want_loss, *want = split_grads(params["frozen"], ld, ls, tokens[:, :-1],
                                   tokens[:, 1:], cfg=cfg, cut=cut,
                                   impl="naive", compress=False)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree_util.tree_leaves(want)) > 0
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(want),
                            strict=True):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b,
                                   atol=1e-4 * np.abs(b).max(initial=0.0),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("cut_frac", [0.0, 0.5, 1.0])
def test_split_grads_match_full_model(setup, cut_frac):
    """Split BP through the channel == end-to-end LoRA grads (phi off)."""
    cfg, params, tokens, labels = setup
    cut = int(cut_frac * cfg.n_layers)

    def loss_fn(lora):
        return M.forward_loss(params["frozen"], lora,
                              {"tokens": tokens, "labels": labels}, cfg,
                              impl="naive", remat=False)

    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params["lora"])
    ld, ls = split_lora(params["lora"], cut)
    loss, gd, gs = split_grads(params["frozen"], ld, ls, tokens, labels,
                               cfg=cfg, cut=cut, compress=False)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    merged = merge_lora(gd, gs)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(ref_grads), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_split_merge_roundtrip(setup):
    cfg, params, *_ = setup
    for cut in (0, 1, cfg.n_layers):
        d, s = split_lora(params["lora"], cut)
        m = merge_lora(d, s)
        for a, b in zip(jax.tree_util.tree_leaves(m),
                        jax.tree_util.tree_leaves(params["lora"]),
                        strict=True):
            assert a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_channel_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 3.0
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    xq = dequantize_int8(q, s, x.dtype)
    # max error bounded by one quantization step per row
    step = np.asarray(s).squeeze()
    err = np.abs(np.asarray(xq - x))
    assert (err <= step[:, None] * 0.5 + 1e-6).all()


def test_channel_compress_straight_through_gradient():
    """d/dx of the quantized channel must be identity (STE)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (16,))
    g = jax.grad(lambda v: jnp.sum(channel_compress(v, True) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0, rtol=1e-6)


def test_compression_changes_forward_but_not_much(setup):
    cfg, params, tokens, labels = setup
    ld, ls = split_lora(params["lora"], 1)
    loss_c, *_ = split_grads(params["frozen"], ld, ls, tokens, labels,
                             cfg=cfg, cut=1, compress=True)
    loss_n, *_ = split_grads(params["frozen"], ld, ls, tokens, labels,
                             cfg=cfg, cut=1, compress=False)
    assert float(loss_c) != float(loss_n)          # quantization is real
    assert abs(float(loss_c) - float(loss_n)) < 0.1  # but small


def test_smashed_data_shape(setup):
    """Eq. 2: smashed data is (B, S, d) at every cut."""
    cfg, params, tokens, _ = setup
    for cut in (0, 1, 2):
        sm = device_forward(params["frozen"],
                            split_lora(params["lora"], cut)[0],
                            tokens, cfg, cut)
        assert sm.shape == (4, 32, cfg.d_model)


def test_executor_caches_programs(setup):
    cfg, params, tokens, labels = setup
    ex = SplitExecutor(cfg, compress=True)
    batch = {"tokens": tokens, "labels": labels}
    l1, g1 = ex.step(params["frozen"], params["lora"], batch, 1)
    l2, g2 = ex.step(params["frozen"], params["lora"], batch, 1)
    assert float(l1) == pytest.approx(float(l2))
    assert jax.tree_util.tree_structure(g1) == \
        jax.tree_util.tree_structure(params["lora"])
