"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described rather than attached. That catches what interpret mode cannot:
lowerings Mosaic lacks, tiles it refuses, programs that do not fit HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file. Kernels are called with ``interpret=False`` directly,
because ``kernels.ops`` asks ``jax.default_backend()``, which is still the
CPU here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.splitting import SplitExecutor, split_grads, split_lora
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import lora_matmul as lm
from repro.kernels import ops
from repro.kernels import ssd_scan as ssd
from repro.models import model as model_lib

QWEN = get_config("qwen3-0.6b")
MAMBA = get_config("mamba2-370m")
GRANITE = get_config("granite-4.0-h-micro")
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_lora_matmul_compiles(one_chip):
    """The MLP up-projection of a 4x512 split step: (2048, 1024) @ (1024,
    3072) with a rank-16 adapter."""
    d, f, r, m = QWEN.d_model, QWEN.d_ff, QWEN.lora.rank, 4 * 512
    args = (_shape(one_chip, (m, d)), _shape(one_chip, (d, f)),
            _shape(one_chip, (d, r), jnp.float32),
            _shape(one_chip, (r, f), jnp.float32))
    _assert_kernel(jax.jit(lambda x, w, a, b: lm.lora_matmul(
        x, w, a, b, QWEN.lora.scale)).lower(*args).compile())


def test_lora_matmul_grouped_compiles(one_chip):
    """One decode tick of 8 slots over a bank of 2 adapters."""
    d, f, r = QWEN.d_model, QWEN.d_ff, QWEN.lora.rank
    args = (_shape(one_chip, (8, 1, d)), _shape(one_chip, (d, f)),
            _shape(one_chip, (2, d, r), jnp.float32),
            _shape(one_chip, (2, r, f), jnp.float32),
            _shape(one_chip, (8,), jnp.int32))
    _assert_kernel(jax.jit(lambda x, w, a, b, i: lm.lora_matmul_grouped(
        x, w, a, b, i, QWEN.lora.scale)).lower(*args).compile())


@pytest.mark.parametrize("window", [0, 256])
def test_flash_attention_compiles(one_chip, window):
    """Prefill/train attention at 4x512, heads folded as ops does it."""
    bh = 4 * QWEN.n_heads
    qkv = [_shape(one_chip, (bh, 512, QWEN.resolved_head_dim))] * 3
    _assert_kernel(jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window)).lower(*qkv).compile())


def test_flash_decode_compiles(one_chip):
    """One-token decode over 8 slots of a 1024-position cache."""
    hd, hkv = QWEN.resolved_head_dim, QWEN.n_kv_heads
    group = QWEN.n_heads // hkv
    args = (_shape(one_chip, (8 * hkv, group, hd)),
            _shape(one_chip, (8 * hkv, 1024, hd)),
            _shape(one_chip, (8 * hkv, 1024, hd)),
            _shape(one_chip, (), jnp.int32))
    _assert_kernel(jax.jit(lambda q, k, v, t: fd.flash_decode(
        q, k, v, t)).lower(*args).compile())


def test_ssd_intra_chunk_compiles(one_chip):
    """mamba2-370m widths: 32 heads of 64, state 128, chunk 256."""
    nh = MAMBA.ssm_d_inner // MAMBA.ssm_head_dim
    b, nc, cl = 2, 2, MAMBA.ssm_chunk
    args = (_shape(one_chip, (b, nc, cl, nh, MAMBA.ssm_head_dim),
                   jnp.float32),
            _shape(one_chip, (b, nc, cl, nh), jnp.float32),
            _shape(one_chip, (b, nc, cl, MAMBA.ssm_state), jnp.float32),
            _shape(one_chip, (b, nc, cl, MAMBA.ssm_state), jnp.float32))
    _assert_kernel(jax.jit(
        lambda *a: ssd.ssd_intra_chunk(*a)).lower(*args).compile())


def test_split_step_fits_one_chip(one_chip):
    """The default split step (naive attention, no remat) of qwen3-0.6b at
    published widths and the paper's Table II batch, 4 x 512 tokens. Every
    cut runs all 28 layers once, so the cut hardly moves the footprint."""
    cut = 6
    params = model_lib.abstract_params(QWEN)
    lora_dev, lora_srv = jax.eval_shape(lambda l: split_lora(l, cut),
                                        params["lora"])
    tokens = _shape(one_chip, (4, 512), jnp.int32)
    compiled = jax.jit(
        lambda fr, ld, ls, x, y: split_grads(fr, ld, ls, x, y, cfg=QWEN,
                                             cut=cut)
    ).lower(_placed(one_chip, params["frozen"]),
            _placed(one_chip, lora_dev), _placed(one_chip, lora_srv),
            tokens, tokens).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB"


_FUSED = {}


def _fused_step(one_chip, cfg, impl="naive"):
    """``SplitExecutor``'s step at 4 x 512 tokens and cut 0, compiled once
    per configuration and attention path."""
    if (cfg.name, impl) not in _FUSED:
        params = model_lib.abstract_params(cfg)
        tokens = _shape(one_chip, (4, 512), jnp.int32)
        _FUSED[cfg.name, impl] = SplitExecutor(cfg, impl=impl).compiled_step \
            .lower(_placed(one_chip, params["frozen"]),
                   _placed(one_chip, params["lora"]), tokens, tokens,
                   cut=0).compile()
    return _FUSED[cfg.name, impl]


def _fused_step_bytes(one_chip, cfg):
    """The compiler's count of the bytes ``SplitExecutor``'s step holds at
    4 x 512 tokens: arguments, outputs and temporaries."""
    mem = _fused_step(one_chip, cfg).memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_fused_split_step_fits_one_chip(one_chip):
    """The program a local epoch runs, ``SplitExecutor``'s step (adapters
    split at the cut, both stages, gradients merged), at the cut CARD picks
    for the Table II fleet."""
    used = _fused_step_bytes(one_chip, QWEN)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB"


@pytest.mark.parametrize("cfg", [GRANITE, MAMBA], ids=lambda c: c.name)
def test_ssm_split_step_fits_one_chip(one_chip, cfg):
    """granite-4.0-h-micro whole (3.19 B parameters, 6.4 GB of bfloat16
    weights) and mamba2-370m, at the cut CARD picks (0): the Mamba layers'
    recomputation keeps the step inside one chip."""
    used = _fused_step_bytes(one_chip, cfg)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB"


@pytest.mark.parametrize("cfg", [QWEN, GRANITE], ids=lambda c: c.name)
def test_flash_split_step_compiles(one_chip, cfg, monkeypatch):
    """The fused step as a TPU runs it, attention on the flash kernels
    (forward, dQ and dK/dV): they compile into it, none of the naive
    path's (KV head, group, S, S) score buffers is left, and it holds fewer
    temporaries than the naive step (qwen3: about 7.4 GB against 10.0)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    flash = _fused_step(one_chip, cfg, impl="pallas")
    naive = _fused_step(one_chip, cfg)
    text = flash.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"%{name}." in text
    scores = f"{cfg.n_kv_heads},{cfg.n_heads // cfg.n_kv_heads},512,512]"
    assert scores in naive.as_text() and scores not in text
    assert (flash.memory_analysis().temp_size_in_bytes
            < naive.memory_analysis().temp_size_in_bytes)
