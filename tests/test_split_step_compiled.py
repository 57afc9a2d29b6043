"""A local epoch of ``SplitFineTuner`` is two compiled programs: the split
step (``split_grads_full``: adapters split at the cut, both stages, the
gradients merged) and the optimizer update. They give the numbers of the
eager sequence they replace, donate nothing, and compile nothing once warm."""
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.channel import WirelessChannel
from repro.core.faults import (CircuitBreaker, FaultInjector, FaultModel,
                               RetryPolicy)
from repro.core.hardware import EDGE_FLEET, SERVER_RTX4060TI, SimParams
from repro.core.protocol import SplitFineTuner
from repro.core.splitting import (SplitExecutor, merge_lora, split_grads,
                                  split_lora)
from repro.models import model as M
from repro.optim import adamw, apply_updates, constant_schedule, sgd

OPTIMIZERS = {"adamw": lambda: adamw(constant_schedule(1e-3)),
              "sgd": lambda: sgd(constant_schedule(1e-2))}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


class _Tokens:
    def __init__(self, vocab, seed):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)

    def minibatch(self, batch, seq_len):
        t = self.rng.integers(0, self.vocab, (batch, seq_len + 1), np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _tuner(cfg, params, opt, *, cut=None, n_dev=1, epochs=3, **kw):
    policy = {"policy": "static", "static_cut": cut} if cut is not None \
        else {"policy": "card"}
    return SplitFineTuner(
        cfg, params["frozen"], params["lora"], opt,
        devices=list(EDGE_FLEET[:n_dev]), server=SERVER_RTX4060TI,
        channels=[WirelessChannel("normal", seed=m) for m in range(n_dev)],
        datasets=[_Tokens(cfg.vocab_size, m) for m in range(n_dev)],
        sim=SimParams(local_epochs=epochs, mini_batch=2, seq_len=16),
        **policy, **kw)


class _Compiles:
    """Names of the programs XLA compiles inside the ``with`` block."""

    def __enter__(self):
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.names.append(kw.get("fun_name"))

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _host(tree):
    return jax.tree_util.tree_map(np.array, jax.device_get(tree))


def _assert_trees_close(got, want, rtol):
    """Leaf by leaf within ``rtol`` of each element or of the leaf's largest
    element: an element that sums terms to near zero (``b1 * m + (1 - b1) *
    g``) keeps the rounding of its terms, not of its value."""
    got_l, got_t = jax.tree_util.tree_flatten(got)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    assert got_t == want_t
    for a, b in zip(got_l, want_l, strict=True):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=rtol,
                                   atol=rtol * np.max(np.abs(b), initial=0))


def _assert_trees_equal(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_compiled_steps_match_the_eager_sequence(tiny, where, opt_name):
    """Each of three steps, from the state the tuner reached, against the
    eager sequence from that same state. (Chained over steps, a last-bit
    difference of AdamW moves the next gradient, and AdamW's normalization
    lifts the elements whose gradient is near zero to the size of a whole
    update.)"""
    cfg, params = tiny
    cut = {"first": 0, "middle": cfg.n_layers // 2,
           "last": cfg.n_layers}[where]
    opt = OPTIMIZERS[opt_name]()
    tuner = _tuner(cfg, params, OPTIMIZERS[opt_name](), cut=cut, epochs=1)
    data = _Tokens(cfg.vocab_size, 0)
    for k in range(1, 4):
        lora, state = tuner.lora, tuner.opt_state
        b = data.minibatch(2, 16)
        lora_dev, lora_srv = split_lora(lora, cut)
        _, g_dev, g_srv = split_grads(params["frozen"], lora_dev, lora_srv,
                                      b["tokens"], b["labels"], cfg=cfg,
                                      cut=cut)
        updates, state = opt.update(merge_lora(g_dev, g_srv), state, lora)
        lora = apply_updates(lora, updates)
        tuner.run(1)
        assert int(tuner.opt_state["step"]) == int(state["step"]) == k
        _assert_trees_close(tuner.lora, lora, rtol=1e-5)
        _assert_trees_close(tuner.opt_state, state, rtol=1e-5)


def test_a_round_leaves_the_state_it_started_from(tiny):
    cfg, params = tiny
    tuner = _tuner(cfg, params, adamw(constant_schedule(1e-3)), n_dev=2,
                   epochs=2)
    tuner.run(1)
    before = (tuner.lora, tuner.opt_state)
    copied = _host(before)
    tuner.run(1)
    _assert_trees_equal(before, copied)
    assert int(tuner.opt_state["step"]) == int(before[1]["step"]) + 4


def test_quorum_rollback_restores_the_round_bit_for_bit(tiny):
    cfg, params = tiny
    tuner = _tuner(cfg, params, adamw(constant_schedule(1e-3)), n_dev=2,
                   epochs=2)
    tuner.run(1)
    before = (tuner.lora, tuner.opt_state)
    copied = _host(before)
    # device 0 trains its whole round, device 1 fails every exchange: one
    # of two survives, under a quorum of 1, so the round rolls back
    real = FaultModel().realize(2, 2, seed=0)
    real.dropout[:, 1] = True
    tuner.fault_injector = FaultInjector(real)
    tuner.retry_policy = RetryPolicy(max_attempts=2, base_backoff_s=0.0)
    tuner.breaker = CircuitBreaker(failure_threshold=99)
    tuner.quorum = 1.0
    res = tuner.run(1)
    assert [log.status for log in res.logs] == ["rolled_back", "dropped"]
    assert not res.round_summaries[0].committed
    _assert_trees_equal(before, copied)
    assert tuner.lora is before[0] and tuner.opt_state is before[1]
    _assert_trees_equal((tuner.lora, tuner.opt_state), copied)


def test_a_warm_round_compiles_nothing(tiny):
    cfg, params = tiny
    tuner = _tuner(cfg, params, adamw(constant_schedule(1e-3)), n_dev=2,
                   epochs=2)
    tuner.run(1)
    with _Compiles() as compiled:
        res = tuner.run(1)
    assert all(log.status == "ok" for log in res.logs)
    assert compiled.names == []


def test_a_step_is_one_program_named_for_the_split_step(tiny):
    cfg, params = tiny
    cut = cfg.n_layers // 2
    tokens = np.zeros((2, 16), np.int32)
    executor = SplitExecutor(cfg)
    text = executor.compiled_step.lower(params["frozen"], params["lora"],
                                        tokens, tokens, cut=cut).as_text()
    assert re.findall(r"^module @(\S+)", text, re.M) == [
        "jit_split_grads_full"]
    with _Compiles() as compiled:
        executor.step(params["frozen"], params["lora"],
                      {"tokens": tokens, "labels": tokens}, cut)
    assert compiled.names == ["jit(split_grads_full)"]


def test_the_update_is_one_program_named_apart_from_the_step(tiny):
    cfg, params = tiny
    tuner = _tuner(cfg, params, adamw(constant_schedule(1e-3)), epochs=1)
    grads = jax.tree_util.tree_map(np.ones_like, _host(params["lora"]))
    args = (grads, tuner.opt_state, tuner.lora)
    modules = re.findall(r"^module @(\S+)",
                         tuner._optimizer_step.lower(*args).as_text(), re.M)
    assert len(modules) == 1
    assert not modules[0].startswith("jit_split_grads")
    with _Compiles() as compiled:
        tuner._optimizer_step(*args)
    assert len(compiled.names) == 1
