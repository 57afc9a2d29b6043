"""The work the configuration's reference counts, against a hand count.

Run by hand: ``python -m pytest chipbench/tests``.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import tiny  # noqa: E402


def cell(name):
    return tiny.files_cell(*tiny.CELLS[name])


def test_qwen3_split_step_flops_match_hand_count():
    # qwen3-0.6b, 4 x 512 tokens, cut 0. Per token and layer:
    #   projections 2*1024*(2048+1024+1024) + 2*2048*1024 = 12,582,912
    #   MLP 3 * 2*1024*3072                               = 18,874,368
    #   LoRA r=16: 2*16*(3072+2048+2048+3072+3*4096)      =    720,896
    #   attention QK^T + PV at (512+1)/2 keys: 4*16*128*256.5 = 2,101,248
    # forward + activation backward: 2x projections and MLP, 3x LoRA and
    # attention; the first layer's q/k/v input gradient is not needed;
    # head 2 * 2*1024*151936.
    tok = 4 * 512
    layer = 2 * (12_582_912 + 18_874_368) + 3 * (720_896 + 2_101_248)
    first = 2 * 1024 * 4096 + 3 * 2 * 16 * 1024
    head = 4 * 1024 * 151936
    hand = tok * (28 * layer - first + head)
    c = cell("split.qwen3-0.6b.table2")
    got = c.reference.train_flops(c.config, 4, 512, 0)
    assert abs(got - hand) <= 1e-9 * hand
    assert 5.3e12 < got < 5.4e12

