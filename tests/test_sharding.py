"""Sharding rules: per-arch param specs on the production meshes
(AbstractMesh — no devices needed, pure divisibility logic)."""
import jax
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config
from repro.launch import sharding as shd
from repro.models import model as M

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_product(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch, mesh):
    """Every sharded dim must be exactly divisible by its axis product."""
    cfg = get_config(arch)
    avals = M.abstract_params(cfg)
    specs = shd.param_specs(cfg, avals, mesh)
    flat_a, _ = jax.tree_util.tree_flatten(avals)
    flat_s, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_a) == len(flat_s)
    for aval, spec in zip(flat_a, flat_s, strict=True):
        assert len(spec) <= len(aval.shape)
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            k = _axis_product(mesh, axis)
            assert aval.shape[dim] % k == 0, \
                f"{arch}: shape {aval.shape} dim {dim} not divisible by {k}"


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "kimi-k2-1t-a32b",
                                  "internvl2-26b"])
def test_big_models_fit_per_chip(arch):
    """Frozen weights per chip (after sharding) must fit 16 GB HBM."""
    cfg = get_config(arch)
    avals = M.abstract_params(cfg)
    specs = shd.param_specs(cfg, avals, MESH_2POD)
    total = 0
    flat_a, _ = jax.tree_util.tree_flatten(avals["frozen"])
    flat_s, _ = jax.tree_util.tree_flatten(
        specs["frozen"], is_leaf=lambda x: isinstance(x, P))
    for aval, spec in zip(flat_a, flat_s, strict=True):
        shards = 1
        for axis in spec:
            shards *= _axis_product(MESH_2POD, axis)
        total += aval.size * aval.dtype.itemsize / shards
    assert total < 12e9, f"{arch}: {total / 1e9:.1f} GB of weights per chip"


def test_moe_layouts_match_strategy():
    from repro.models.moe_shard_map import strategy_for_mesh
    kimi = get_config("kimi-k2-1t-a32b")
    granite = get_config("granite-moe-3b-a800m")
    assert strategy_for_mesh(kimi, MESH_1POD) == "ep_a2a"
    assert strategy_for_mesh(kimi, MESH_2POD) == "ep_a2a"
    # 40 experts don't divide 16/32 -> replicated (weights ~3 GB)
    assert strategy_for_mesh(granite, MESH_1POD) == "replicated"
    specs = shd.param_specs(granite, M.abstract_params(granite), MESH_1POD)
    moe_specs = specs["frozen"]["layers"]["moe"]
    assert moe_specs["w_gate"] == P(None, None, None, None)


def test_vocab_padding_shards():
    """Odd vocabs pad to 256-multiples => vocab dim shards over 16."""
    for arch in ("internvl2-26b", "hymba-1.5b", "mamba2-370m",
                 "granite-moe-3b-a800m"):
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
        avals = M.abstract_params(cfg)
        specs = shd.param_specs(cfg, avals, MESH_1POD)
        embed_spec = specs["frozen"]["embed"]
        assert embed_spec[0] == "model", f"{arch}: embed vocab not sharded"


def test_batch_specs_guard_small_batch():
    s = shd.batch_specs_for(get_config("qwen3-4b"), MESH_1POD, "decode",
                            global_batch=1)
    assert s["tokens"] == P(None, None)
    s2 = shd.batch_specs_for(get_config("qwen3-4b"), MESH_1POD, "train",
                             global_batch=256)
    assert s2["tokens"][0] in ("data", ("data",))


def test_cut_batch_specs_are_smashed():
    s = shd.batch_specs_for(get_config("qwen3-0.6b"), MESH_1POD, "train",
                            global_batch=256, cut=14)
    assert set(s) == {"smashed", "labels"}
