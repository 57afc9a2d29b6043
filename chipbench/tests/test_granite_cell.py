"""The Granite 4.0-H cell at a size a CPU can hold: the real harness,
runner and reference over a short stack that keeps the published mix,
held to the cell's committed limits; and the work its reference counts,
against a hand count.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""
import copy
import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import tiny  # noqa: E402
import run as bench  # noqa: E402
from chipbench import harness  # noqa: E402

CELL = "split.granite-4.0-h-micro.table2"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "shared_intermediate_size": 128,
        "vocab_size": 256, "num_hidden_layers": 6,
        "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                        "attention"],
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_chunk_size": 16}


def tiny_cell():
    cell = tiny.files_cell("granite-4.0-h-micro", "table2")
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config),
                               traffic=dict(cell.traffic, seq_len=40))
    cell.config.update(TINY)
    cell.config["lora"].update(tiny.TINY_RANK)
    cell.limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                                 CELL + ".json"))
    return cell


def run(control=0):
    result, comparison = bench.run_cell(
        tiny.args(CELL, seconds=1.0, control=control), require_tpu=False,
        cell=tiny_cell())
    return result["correct"], comparison.numbers


def test_sound_granite_is_correct():
    ok, numbers = run()
    assert ok, numbers


def test_granite_control_is_not_correct():
    """The reference one precision lower, in the program's place."""
    ok, numbers = run(control=1)
    assert not ok, numbers


def test_granite_split_step_flops_match_hand_count():
    # granite-4.0-h-micro, 4 x 512 tokens, cut 0. Per token:
    #   Mamba layer: in_proj 2*2048*8512 + out_proj 2*4096*2048 + MLP
    #     3 * 2*2048*8192 = 152,305,664 (2x); conv 2*4*4352 = 34,816 (2x);
    #     LoRA r=16 on in/out_proj and the MLP 32*47,424 = 1,517,568 (3x);
    #     SSD at chunk 256: 257*(128 + 64*64) + 4*128*64*64 = 3,182,720 (3x)
    #   attention layer: q/k/v/o 2*2048*(2048+512+512+2048) + MLP
    #     = 121,634,816 (2x); LoRA 32*44,032 = 1,409,024 (3x);
    #     QK^T + PV at 256.5 keys 4*32*64*256.5 = 2,101,248 (3x)
    #   no input gradient into layer 0 (Mamba): in_proj 2*2048*8512 and
    #     its adapter's 2*16*2048; head 2 * 2*2048*100352.
    mamba = 2 * (152_305_664 + 34_816) + 3 * (1_517_568 + 3_182_720)
    attn = 2 * 121_634_816 + 3 * (1_409_024 + 2_101_248)
    first = 2 * 2048 * 8512 + 2 * 16 * 2048
    hand = 4 * 512 * (36 * mamba + 4 * attn - first + 4 * 2048 * 100352)
    c = tiny.files_cell("granite-4.0-h-micro", "table2")
    got = c.reference.train_flops(c.config, 4, 512, 0)
    assert abs(got - hand) <= 1e-9 * hand
    assert 27.1e12 < got < 27.3e12
