"""Cells at a size a CPU test can hold: the real harness, runner and
reference over a two-layer cut of the configuration."""
from __future__ import annotations

import argparse
import copy
import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

TINY_DENSE = {"num_hidden_layers": 2, "hidden_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "intermediate_size": 256, "vocab_size": 512}
TINY_RANK = {"rank": 4, "alpha": 8.0}


def files_cell(config: str, mix: str) -> harness.Cell:
    """A cell made from a configuration file and a mix file alone, as a
    later change's cell would be; no limits, no metrics."""
    return harness.Cell(
        name=f"{mix}-test", chips=1,
        config=harness.load_json(os.path.join(harness.HERE, "configs",
                                              config + ".json")),
        traffic=harness.load_json(os.path.join(harness.HERE, "traffic",
                                               mix + ".json")),
        limits={}, end_to_end=[], per_layer=[])


CELLS = {"split.qwen3-0.6b.table2": ("qwen3-0.6b", "table2")}


def tiny_cell(name: str, **traffic) -> harness.Cell:
    """A cell's configuration cut to two small layers under its mix, held
    to the cell's committed limits; ``traffic`` overrides the mix's
    parameters."""
    cell = files_cell(*CELLS[name])
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config),
                               traffic=dict(cell.traffic, **traffic))
    cell.config.update(TINY_DENSE)
    cell.config["lora"].update(TINY_RANK)
    cell.limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                                 name + ".json"))
    return cell


def args(workload: str, seed: int = 1234567890123, seconds: float = 1.0,
         trace: int = 0, control: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, control=control)
