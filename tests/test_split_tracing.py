"""The split path's trace names: named scopes inside ``split_grads`` reach
the compiled program's op metadata, forward and backward, and one round of
``SplitFineTuner`` opens each host span as often as the protocol implies."""
import glob
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.channel import WirelessChannel
from repro.core.hardware import EDGE_FLEET, SERVER_RTX4060TI, SimParams
from repro.core.protocol import SplitFineTuner
from repro.core.splitting import (SCOPE_DEVICE_STAGE, SCOPE_HEAD,
                                  SCOPE_SERVER_LAYERS, SCOPES, SPAN_BATCH,
                                  SPAN_DECIDE, SPAN_DISPATCH, SPAN_LOSS_SYNC,
                                  SPAN_OPTIMIZER, SPAN_ROUND, SPANS,
                                  split_grads, split_lora)
from repro.models import model as M
from repro.models.mamba import SCOPE_SSM_MIXER, SCOPE_SSM_SCAN
from repro.optim import adamw, constant_schedule


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def op_names(tiny):
    """Every ``op_name`` of the compiled split step at a mid cut."""
    cfg, params = tiny
    cut = cfg.n_layers // 2
    assert 0 < cut < cfg.n_layers
    lora_dev, lora_srv = split_lora(params["lora"], cut)
    tokens = jnp.zeros((2, 16), jnp.int32)
    text = split_grads.lower(params["frozen"], lora_dev, lora_srv, tokens,
                             tokens, cfg=cfg, cut=cut,
                             compress=True).compile().as_text()
    return set(re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text))


def test_names_are_distinct_and_prefixed():
    names = SCOPES + SPANS
    assert len(set(names)) == len(names) == 10
    assert all(n.startswith("sl.") for n in names)


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_reaches_forward_ops(op_names, scope):
    # forward: under jvp(...) or, for the downlink, the scope itself
    fwd = [n for n in op_names
           if re.search(rf"(^|/)(jvp\()?{re.escape(scope)}[)/]", n)]
    assert fwd, scope


@pytest.mark.parametrize("scope", [SCOPE_DEVICE_STAGE, SCOPE_SERVER_LAYERS,
                                   SCOPE_HEAD])
def test_scope_reaches_backward_ops(op_names, scope):
    assert any(f"transpose(jvp({scope}))" in n for n in op_names), scope


def test_scopes_are_disjoint(op_names):
    for n in op_names:
        assert len(set(re.findall(r"sl\.\w+", n))) <= 1, n


class _Tokens:
    def __init__(self, vocab, seed):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)

    def minibatch(self, batch, seq_len):
        t = self.rng.integers(0, self.vocab, (batch, seq_len + 1), np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name in SPANS]
    return out


@pytest.fixture(scope="module")
def traced_round(tiny, tmp_path_factory):
    """One ``run(1)`` over two devices of two local epochs, traced."""
    cfg, params = tiny
    n_dev, epochs = 2, 2
    tuner = SplitFineTuner(
        cfg, params["frozen"], params["lora"],
        adamw(constant_schedule(1e-3)), devices=list(EDGE_FLEET[:n_dev]),
        server=SERVER_RTX4060TI,
        channels=[WirelessChannel("normal", seed=m) for m in range(n_dev)],
        datasets=[_Tokens(cfg.vocab_size, m) for m in range(n_dev)],
        sim=SimParams(local_epochs=epochs, mini_batch=2, seq_len=16))
    tuner.run(1)                 # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        res = tuner.run(1)
    assert all(log.status == "ok" for log in res.logs)
    return _host_spans(trace_dir), n_dev, epochs


def test_each_span_counts_as_the_protocol_implies(traced_round):
    spans, n_dev, epochs = traced_round
    counts = Counter(name for name, _, _ in spans)
    per_round = {SPAN_ROUND, SPAN_DECIDE, SPAN_LOSS_SYNC}
    per_epoch = {SPAN_BATCH, SPAN_DISPATCH, SPAN_OPTIMIZER}
    assert per_round | per_epoch == set(SPANS)
    assert counts == {**{n: n_dev for n in per_round},
                      **{n: n_dev * epochs for n in per_epoch}}


def test_spans_nest_in_their_round(traced_round):
    spans, _, _ = traced_round
    rounds = [(s, e) for name, s, e in spans if name == SPAN_ROUND]
    for name, s, e in spans:
        if name != SPAN_ROUND:
            assert any(r0 <= s and e <= r1 for r0, r1 in rounds), name


# ---- the Mamba-2 mixer's scopes inside the fused step ----------------------


@pytest.fixture(scope="module")
def ssm_op_names():
    """Every ``op_name`` of the fused split step of a short Granite 4.0-H
    stack (mamba, mamba, attention) cut inside its Mamba run, so that both
    stages hold a Mamba layer."""
    import dataclasses
    from repro.core.splitting import SplitExecutor
    cfg = dataclasses.replace(
        get_config("granite-4.0-h-micro").reduced(), n_layers=3,
        layer_types=("mamba", "mamba", "attention"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    text = SplitExecutor(cfg).compiled_step.lower(
        params["frozen"], params["lora"], tokens, tokens,
        cut=1).compile().as_text()
    return set(re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text))


@pytest.mark.parametrize("scope", [SCOPE_SSM_MIXER, SCOPE_SSM_SCAN])
@pytest.mark.parametrize("stage", [SCOPE_DEVICE_STAGE, SCOPE_SERVER_LAYERS])
def test_ssm_scopes_reach_the_fused_step(ssm_op_names, scope, stage):
    """Forward under ``jvp(<stage>)``, backward under
    ``transpose(jvp(<stage>))``, each with the scope inside the stage."""
    inside = [n for n in ssm_op_names if f"/{scope}/" in n
              and (scope == SCOPE_SSM_MIXER or f"/{SCOPE_SSM_MIXER}/" in n)]
    fwd = [n for n in inside if f"/jvp({stage})/" in n]
    bwd = [n for n in inside if f"/transpose(jvp({stage}))/" in n]
    assert fwd and bwd, (scope, stage)
