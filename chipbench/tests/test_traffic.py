"""The split mix's data: one seed always gives the same batches, and each
device its own stream. Run by hand: ``python -m pytest chipbench/tests``."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import tiny  # noqa: E402


def _batches(seed, device, n=3):
    runner = tiny.files_cell("qwen3-0.6b", "table2").runner
    stream = runner.TokenStream(151936, seed, device)
    return [stream.minibatch(4, 512) for _ in range(n)]


def test_same_seed_same_batches():
    a, b = _batches(2**33 + 17, 1), _batches(2**33 + 17, 1)
    for x, y in zip(a, b, strict=True):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert np.array_equal(x["labels"], y["labels"])


def test_streams_differ_by_seed_and_device():
    base = _batches(5, 0)[0]["tokens"]
    assert not np.array_equal(base, _batches(6, 0)[0]["tokens"])
    assert not np.array_equal(base, _batches(5, 1)[0]["tokens"])


def test_labels_are_the_next_tokens():
    b = _batches(2147483711, 2, n=1)[0]
    assert b["tokens"].shape == b["labels"].shape == (4, 512)
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 151936
