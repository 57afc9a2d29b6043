"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``record_trace.py``: three rounds of a matmul chain and an elementwise
program, each inside a benchmark span, with a 2 ms host pause between."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(HERE), "testdata")


def test_merge_and_clip():
    assert trace.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.clip([(0, 4), (5, 9)], 1, 6) == [(1, 4), (5, 6)]


def test_names():
    assert trace.program_name("jit_split_grads(1234)") == "jit_split_grads"
    assert trace.op_name("%fusion.3 = bf16[2]{0} fusion(%x)") == "fusion.3"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(DATA)


def test_programs_and_busy_union(reduced):
    progs = reduced["programs"]
    assert progs["jit_matmul_chain"]["count"] == 3
    assert progs["jit_elementwise"]["count"] == 3
    total = sum(p["seconds"] for p in progs.values())
    # one chip, programs never overlap: the busy union is their sum
    assert reduced["busy_s"] == pytest.approx(total, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert 0.5 < reduced["idle_share"] < 1.0


def test_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert ops and all(v > 0 for v in ops.values())
    gaps = reduced["idle_gaps"]
    assert gaps and all(s > 0 for _, s in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert any("bench.host_pause" in name for name, _ in gaps)


def test_execution_across_an_edge_counts_by_its_share(monkeypatch):
    # a 10 ns window; a 4 ns program half inside at each edge and one whole
    raw = {"devices": [{"name": "/device:TPU:0", "ops": [], "modules": [
               ("jit_step", 98, 102, None), ("jit_step", 104, 108, None),
               ("jit_step", 108, 112, None)]}],
           "spans": [(trace.WINDOW_SPAN, 100, 110)], "offset_ns": 0}
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "read", lambda path: raw)
    secs, count = trace.program_seconds(trace.reduce("unused"), "jit_step")
    assert count == pytest.approx(2.0)
    assert secs == pytest.approx(8e-9)
    assert secs / count == pytest.approx(4e-9)
