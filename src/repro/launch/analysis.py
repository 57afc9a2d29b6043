"""Compiled-artifact analysis: collective-bytes parsing + roofline terms.

Sources (§Roofline in EXPERIMENTS.md):
  * ``compiled.cost_analysis()``  -> HLO FLOPs, HLO bytes accessed
  * ``lowered/compiled.as_text()`` -> collective ops; we sum each
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute result size (bytes moved per device, SPMD view)

Hardware peaks come from ``core.hardware.chip_peaks``, keyed by device
kind; the production meshes are TPU v5e chips.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Tuple

from repro.core.hardware import TPU_V5E_KIND, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_V5E = chip_peaks(TPU_V5E_KIND)   # the chips of the production meshes

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

# result shapes like: bf16[8,4096,512]{2,1,0:T(8,128)(2,1)} or tuples
_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|f16|c64|c128)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*\)|[^ ]+)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"[\s(]", re.MULTILINE)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def summary(self) -> str:
        parts = [f"{k}: n={self.counts.get(k, 0)} "
                 f"{self.bytes_by_kind.get(k, 0) / 1e9:.3f} GB"
                 for k in COLLECTIVE_KINDS if self.counts.get(k)]
        return "; ".join(parts) if parts else "none"


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op (per-device view)."""
    stats = CollectiveStats()
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        kind = kind.replace("-start", "")
        b = _shape_bytes(shape_str)
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
    return stats


@dataclass
class Roofline:
    """Per-device roofline terms, seconds."""
    flops: float                 # HLO FLOPs (per device)
    hbm_bytes: float             # HLO bytes accessed (per device)
    collective_bytes: float      # per device
    chips: int
    ici_links: int = 4           # v5e 2D torus: 4 links/chip

    @property
    def compute_s(self) -> float:
        return self.flops / _V5E.bf16_flops_per_s

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / _V5E.hbm_bytes_per_s

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (_V5E.ici_bytes_per_s_per_link
                                        * self.ici_links)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
        }


def model_flops(cfg, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = cfg.active_params()
    factor = 6.0 if kind == "train" else 2.0
    return factor * n * tokens


def analyze_compiled(compiled, lowered_text: str, chips: int) -> Tuple[Roofline, CollectiveStats, Dict]:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(lowered_text)
    roof = Roofline(flops=flops, hbm_bytes=hbm,
                    collective_bytes=float(coll.total_bytes), chips=chips)
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}
    return roof, coll, mem_info
