"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of the split cell's run at a size a CPU can
hold (the look for a chip skipped) against the cell's committed limits,
with one fault planted in the program, and sees ``correct`` come out false;
the sound run beside them comes out true.
Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import tiny  # noqa: E402
import run as bench  # noqa: E402

SPLIT = "split.qwen3-0.6b.table2"


def run(seconds=1.0, control=0):
    cell = tiny.tiny_cell(SPLIT)
    result, comparison = bench.run_cell(
        tiny.args(SPLIT, seconds=seconds, control=control),
        require_tpu=False, cell=cell)
    return result["correct"], comparison.numbers


def test_sound_split_is_correct():
    ok, numbers = run()
    assert ok, numbers


def test_split_state_left_unchanged(monkeypatch):
    import repro.core.protocol as protocol
    monkeypatch.setattr(protocol, "apply_updates", lambda p, u: p)
    ok, numbers = run()
    assert not ok and numbers["update_norm_gap"] > 0.5, numbers


def test_split_half_batch(monkeypatch):
    import repro.core.splitting as splitting
    step = splitting.split_grads

    def half(frozen, ld, ls, inputs, labels, **kw):
        n = inputs.shape[0] // 2
        return step(frozen, ld, ls, inputs[:n], labels[:n], **kw)

    monkeypatch.setattr(splitting, "split_grads", half)
    ok, numbers = run()
    assert not ok, numbers


def test_control_is_not_correct():
    """The reference one precision lower, in the program's place."""
    ok, numbers = run(seconds=2.0, control=1)
    assert not ok, numbers
