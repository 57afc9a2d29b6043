"""Config registry: every assigned arch resolves, with the exact shapes."""
import dataclasses

import pytest

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, all_configs,
                                get_config, long_context_variant)

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
    "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
    "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
    "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
    "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
    "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
    "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
}


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_assigned_config_exact(arch):
    c = get_config(arch)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == EXPECTED[arch]
    assert c.source, "every config must cite its source"


def test_moe_fields():
    g = get_config("granite-moe-3b-a800m")
    assert (g.n_experts, g.top_k) == (40, 8)
    k = get_config("kimi-k2-1t-a32b")
    assert (k.n_experts, k.top_k) == (384, 8)
    assert abs(k.total_params() - 1.04e12) / 1.04e12 < 0.05  # ~1T
    assert abs(k.active_params() - 33e9) / 33e9 < 0.10       # ~32B active


def test_ssm_fields():
    m = get_config("mamba2-370m")
    assert m.ssm_state == 128 and m.is_attention_free
    h = get_config("hymba-1.5b")
    assert h.ssm_state == 16 and h.family == "hybrid"


def test_mixed_stack_fields():
    g = get_config("granite-4.0-h-micro")
    assert g.family == "mixed" and g.has_ssm and not g.is_attention_free
    assert g.kind_counts(0, g.n_layers) == {"mamba": 36, "attention": 4}
    assert g.kind_config("mamba").family == "ssm"
    assert g.kind_config("attention").family == "dense"
    assert (g.kind_index("mamba", 6), g.kind_index("attention", 6)) == (5, 1)
    assert (g.position_embedding, g.attention_multiplier) == ("nope", 1 / 64)
    assert abs(g.total_params() - 3.19e9) / 3.19e9 < 0.01
    with pytest.raises(ValueError):
        g.params_per_layer()
    with pytest.raises(ValueError):
        dataclasses.replace(g, n_layers=39)


def test_reduced_variants_are_small():
    for arch, cfg in all_configs().items():
        r = cfg.reduced()
        assert r.n_layers <= 2 and r.d_model <= 512
        assert r.n_experts <= 4
        assert r.family == cfg.family, arch


def test_long_context_variant():
    # attention archs get a sliding window; ssm runs natively
    d = long_context_variant(get_config("qwen2-7b"))
    assert d.sliding_window == 8192
    m = long_context_variant(get_config("mamba2-370m"))
    assert m.sliding_window == 0
    h = long_context_variant(get_config("hymba-1.5b"))
    assert h.sliding_window == 1024  # keeps its own (smaller) window


def test_input_shapes():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
    # 10 assigned + the paper's llama32-1b + granite-4.0-h-micro
    assert len(ARCH_IDS) == 12
