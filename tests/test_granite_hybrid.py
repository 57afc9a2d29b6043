"""Granite 4.0-H through the split path against its plain reference.

A short stack that keeps the published mix (mamba, mamba, attention,
mamba, mamba, attention) at small widths, weights made from a seed by the
reference (``chipbench/reference/granite_hybrid.py``) in the program's
layout and run by both in float32: logits, the split loss and every
adapter gradient, at cuts 0, inside a Mamba run, just before and just
after an attention layer, and after the last layer (everything on the
device). The reference writes the SSM in its full masked form, the program
chunks it, so the sequence is not a multiple of the chunk.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench import run as bench
from chipbench.reference import common
from chipbench.reference import granite_hybrid as ref
from repro.configs.base import get_config
from repro.core.splitting import SplitExecutor, merge_lora, split_lora
from repro.models import model as M

SEQ = 40          # not a multiple of the chunk (16)
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "shared_intermediate_size": 128,
        "vocab_size": 256, "num_hidden_layers": 6,
        "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                        "attention"],
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 16, "torch_dtype": "float32"}
# cut: 0, inside the first Mamba run, just before and just after the first
# attention layer, everything on the device
CUTS = (0, 1, 2, 3, 6)


def _cell(config):
    return harness.Cell(name="granite-test", chips=1, config=config,
                        traffic={}, limits={}, end_to_end=[], per_layer=[])


@pytest.fixture(scope="module")
def tiny():
    """The configuration file cut to TINY, the program's configuration made
    from it as the benchmark makes it, and float32 weights."""
    cell = _cell(harness.load_json(
        "chipbench/configs/granite-4.0-h-micro.json"))
    c = copy.deepcopy(cell.config)
    c.update(TINY)
    c["lora"].update(rank=4, alpha=8.0)
    cfg = bench.program_config(dataclasses.replace(cell, config=c))
    made = jax.jit(lambda k1, k2: (ref.make_frozen(c, k1),
                                   ref.make_lora(c, k2)))(
        common.key_from_seed(2147600101, 1), common.key_from_seed(2147600101, 2))
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, SEQ + 1), 0,
                                c["vocab_size"])
    return c, cfg, f32(made[0]), made[1], tokens[:, :-1], tokens[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def test_registry_is_the_published_file():
    """The benchmark puts the file's values over the registry entry; the
    entry already holds them, and the reference's weights are the count
    the cost model prices."""
    c = harness.load_json("chipbench/configs/granite-4.0-h-micro.json")
    cfg = get_config("granite-4.0-h-micro")
    assert bench.program_config(_cell(c)) == dataclasses.replace(
        cfg, lora=bench.program_config(_cell(c)).lora)
    assert cfg.layer_types == tuple(c["layer_types"])
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert (cfg.resolved_head_dim, cfg.ssm_n_heads, cfg.ssm_d_inner) == \
        (64, c["mamba_n_heads"], 4096)
    frozen = jax.eval_shape(lambda k: ref.make_frozen(c, k),
                            jax.random.PRNGKey(0))
    n = sum(v.size for v in jax.tree_util.tree_leaves(frozen))
    assert n == cfg.total_params()
    assert 3.19e9 < n < 3.20e9


def test_program_params_have_the_reference_layout(tiny):
    _, cfg, frozen, lora, *_ = tiny
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    assert shapes(params["frozen"]) == shapes(frozen)
    assert shapes(params["lora"]) == shapes(lora)


def test_logits_match_reference(tiny):
    c, cfg, frozen, lora, tokens, _ = tiny
    x, _ = M.forward_hidden(frozen, lora, tokens, cfg, impl="naive",
                            remat=False)
    got = M.logits_from_hidden(frozen, x, cfg)[..., :c["vocab_size"]]
    want = ref.logits(c, frozen, ref.hidden(c, frozen, lora, tokens))
    assert _rel(got, want) < 1e-5   # float32 both sides


@pytest.mark.parametrize("cut", CUTS)
def test_split_loss_and_adapter_grads_match_reference(tiny, cut):
    """The step a local epoch runs (adapters split at the cut per layer
    kind, both stages, gradients merged). With the int8 link both ways the
    loss matches the reference's split loss; the gradients are compared
    with the link off, because where the stages' outputs part by float32
    rounding one int8 code can round the other way and move a leaf by
    about 1e-3."""
    c, cfg, frozen, lora, tokens, labels = tiny
    loss, _ = SplitExecutor(cfg).compiled_step(frozen, lora, tokens, labels,
                                               cut=cut)
    want_loss = jax.jit(lambda lo: ref.split_loss(c, frozen, lo, tokens,
                                                  labels, cut))(lora)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)

    loss, grads = SplitExecutor(cfg, compress=False).compiled_step(
        frozen, lora, tokens, labels, cut=cut)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda lo: common.cross_entropy(ref.logits(
            c, frozen, ref.hidden(c, frozen, lo, tokens)), labels)))(lora)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    got = jax.tree_util.tree_leaves_with_path(grads)
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(got) == len(ref_leaves) == 24   # 7 + 5 adapted projections
    for path, g in got:
        # float32 on both sides; they part only by rounding (about 1e-6)
        assert _rel(g, ref_leaves[path]) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("cut", CUTS)
def test_split_lora_follows_the_layer_index(tiny, cut):
    """Each kind's stack splits at the rows of the layers below the cut,
    and merging gives the stacks back."""
    c, cfg, _, lora, *_ = tiny
    dev, srv = split_lora(lora, cut, cfg)
    below = c["layer_types"][:cut]
    for kind in ("mamba", "attention"):
        d = jax.tree_util.tree_leaves(dev["layers"][kind])[0].shape[0]
        s = jax.tree_util.tree_leaves(srv["layers"][kind])[0].shape[0]
        assert d == below.count(kind)
        assert d + s == c["layer_types"].count(kind)
    merged = merge_lora(dev, srv)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(lora), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_control_precision_is_far_from_reference(tiny):
    """The reference one precision lower (fp8 forward) lands well away
    from itself, so the comparison can tell the two apart."""
    c, _, frozen, lora, tokens, labels = tiny
    grad = lambda prec: jax.jit(jax.grad(lambda lo: ref.split_loss(
        c, frozen, lo, tokens, labels, 0, prec)))(lora)
    f32, fp8 = grad("f32"), grad("fp8")
    worst = max(_rel(a, b) for a, b in zip(jax.tree_util.tree_leaves(fp8),
                                            jax.tree_util.tree_leaves(f32),
                                            strict=True))
    assert worst > 1e-2
