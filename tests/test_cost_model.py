"""Cost model (Sec. III): FLOPs accounting, data sizes, delay/energy laws."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core.channel import ChannelState
from repro.core.cost_model import RoundContext, Workload
from repro.core.hardware import (DEFAULT_SIM, EDGE_FLEET, SERVER_RTX4060TI,
                                 SimParams)

CFG = get_config("llama32-1b")


def ctx_for(cfg=CFG, batch=4, seq=512, device=EDGE_FLEET[0]):
    ch = ChannelState(25.0, 30.0, 20e6)
    return RoundContext(workload=Workload(cfg, batch, seq), device=device,
                        server=SERVER_RTX4060TI, channel=ch, sim=DEFAULT_SIM)


def test_eta_monotone_and_consistent():
    w = Workload(CFG, 4, 512)
    prev = -1.0
    for c in range(CFG.n_layers + 1):
        eta_d = w.device_flops(c)
        assert eta_d > prev
        prev = eta_d
        assert w.server_flops(c) == pytest.approx(
            w.total_flops() - eta_d)
    # eta_D(I) < eta: the head + loss always stay on the server
    assert w.device_flops(CFG.n_layers) < w.total_flops()


def test_uniform_layer_increments():
    """The paper's premise: every decoder layer adds the same FLOPs/bytes."""
    w = Workload(CFG, 4, 512)
    inc = [w.device_flops(c + 1) - w.device_flops(c)
           for c in range(CFG.n_layers)]
    assert np.allclose(inc, inc[0])
    sizes = [w.smashed_bytes(c, 2) for c in range(CFG.n_layers + 1)]
    assert len(set(sizes)) == 1  # constant smashed size across cuts
    ad = [w.adapter_bytes(c + 1, 4) - w.adapter_bytes(c, 4)
          for c in range(CFG.n_layers)]
    assert np.allclose(ad, ad[0]) and ad[0] > 0


def test_moe_counts_active_flops_only():
    moe = get_config("kimi-k2-1t-a32b")
    w = Workload(moe, 1, 128)
    per_layer = w.device_flops(1) - w.device_flops(0)
    # an active-FLOPs layer is ~ top_k/n_experts of a dense-all-experts layer
    dense_equiv = 2 * 2 * 3 * moe.d_model * moe.d_ff * moe.n_experts * 128
    assert per_layer < 0.1 * dense_equiv


@settings(max_examples=30, deadline=None)
@given(c=st.integers(0, 32), f_ghz=st.floats(0.5, 2.4))
def test_delay_energy_laws(c, f_ghz):
    """Eq. 8: server delay ~ 1/f. Eq. 11: energy ~ f^2 (same cut)."""
    ctx = ctx_for()
    f = f_ghz * 1e9
    d1 = ctx.server_comp_delay(c, f)
    d2 = ctx.server_comp_delay(c, 2 * f)
    assert d1 == pytest.approx(2 * d2, rel=1e-9)
    e1 = ctx.server_energy(c, f)
    e2 = ctx.server_energy(c, 2 * f)
    if e1 > 0:
        assert e2 == pytest.approx(4 * e1, rel=1e-9)


def test_transmission_delay_decomposition():
    """Eq. 9: T*(smashed up + grad down) + adapters both ways."""
    ctx = ctx_for()
    sim, ch, w = ctx.sim, ctx.channel, ctx.workload
    c = 7
    expect = (sim.local_epochs
              * (8 * sim.phi * w.smashed_bytes(c, sim.act_bytes) / ch.rate_up
                 + 8 * sim.phi * w.gradient_bytes(c, sim.act_bytes)
                 / ch.rate_down)
              + 8 * w.adapter_bytes(c, sim.adapter_bytes)
              * (1 / ch.rate_up + 1 / ch.rate_down))
    assert ctx.transmission_delay(c) == pytest.approx(expect)


def test_corners_ordering():
    for device in EDGE_FLEET:
        ctx = ctx_for(device=device)
        d_min, d_max, e_min, e_max = ctx.corners()
        assert d_min < d_max
        assert e_min < e_max
        # c=I leaves only the LM head + loss on the server (the paper treats
        # this as ~0; we count it): E_min must be a small fraction of E_max
        assert e_min < 0.05 * e_max
        # cost at the corners is within [0, 1] per term
        f = ctx.server.f_max
        assert 0.0 <= ctx.cost(0, f) <= 2.0


def test_fmin_scales_with_device_power():
    """F_min^{m,S} = f_m delta_m sigma_m / (delta_S sigma_S) (Sec. III-C)."""
    fmins = [ctx_for(device=d).f_min() for d in EDGE_FLEET]
    assert fmins == sorted(fmins, reverse=True)  # faster device, higher floor
    d = EDGE_FLEET[0]
    expect = d.peak_flops / (SERVER_RTX4060TI.delta * SERVER_RTX4060TI.sigma)
    assert fmins[0] == pytest.approx(max(expect, SERVER_RTX4060TI.f_min))


def test_memory_feasibility_mask():
    w = Workload(get_config("phi3-medium-14b"), 1, 128)
    ctx = RoundContext(workload=w, device=EDGE_FLEET[4],  # 4 GB Nano
                       server=SERVER_RTX4060TI,
                       channel=ChannelState(25, 30, 20e6), sim=DEFAULT_SIM)
    # 14B backbone (~29 GB bf16) cannot fit a 4 GB device beyond a few cuts
    assert ctx.max_feasible_cut() <= 4

    w2 = Workload(get_config("qwen3-0.6b"), 1, 128)
    ctx2 = RoundContext(workload=w2, device=EDGE_FLEET[0],  # 32 GB Orin
                        server=SERVER_RTX4060TI,
                        channel=ChannelState(25, 30, 20e6), sim=DEFAULT_SIM)
    assert ctx2.max_feasible_cut() == w2.cfg.n_layers


# ---- per-layer pricing of mixed stacks -----------------------------------

GRANITE = get_config("granite-4.0-h-micro")


@pytest.mark.parametrize("cut", [0, 1, 5, 6, 16, 39, 40])
def test_mixed_stack_prices_the_layers_below_the_cut(cut):
    """Granite 4.0-H: each per-cut quantity is the sum over its real layers
    [0, cut), Mamba and attention priced apart."""
    from repro.core.cost_model import (embed_fwd_flops_per_token,
                                       layer_fwd_flops_per_token)
    w = Workload(GRANITE, 4, 512)
    kinds = GRANITE.layer_types[:cut]
    one = {k: GRANITE.kind_config(k) for k in ("mamba", "attention")}
    flops = sum(layer_fwd_flops_per_token(one[k], 512) for k in kinds)
    assert w.device_flops(cut) == pytest.approx(
        2.0 * (embed_fwd_flops_per_token(GRANITE) + flops) * w.tokens,
        rel=1e-12)
    assert w.device_weight_bytes(cut) == \
        GRANITE.vocab_size * GRANITE.d_model * 2 \
        + sum(one[k].params_per_layer() * 2 for k in kinds)
    assert w.adapter_bytes(cut, 4) == \
        4 * sum(one[k].lora_params_per_layer() for k in kinds)
    assert one["mamba"].params_per_layer() != \
        one["attention"].params_per_layer()


def test_mixed_stack_layer_costs_differ_by_kind():
    w = Workload(GRANITE, 4, 512)
    inc = [w.device_flops(c + 1) - w.device_flops(c)
           for c in range(GRANITE.n_layers)]
    kinds = GRANITE.layer_types
    assert len({round(v) for v, k in zip(inc, kinds) if k == "mamba"}) == 1
    assert len({round(v) for v, k in zip(inc, kinds) if k == "attention"}) \
        == 1
    assert inc[5] < inc[4]          # attention is the cheaper kind here
    assert GRANITE.total_params() == pytest.approx(3.19e9, rel=1e-2)


class _OneLayerTimesCut(Workload):
    """The pricing before mixed stacks: cut x one layer."""

    def device_flops(self, cut):
        from repro.core.cost_model import (embed_fwd_flops_per_token,
                                           layer_fwd_flops_per_token)
        return 2.0 * (embed_fwd_flops_per_token(self.cfg) + cut
                      * layer_fwd_flops_per_token(self.cfg, self.seq_len)
                      ) * self.tokens

    def total_flops(self):
        from repro.core.cost_model import (embed_fwd_flops_per_token,
                                           head_fwd_flops_per_token,
                                           layer_fwd_flops_per_token)
        cfg = self.cfg
        return 2.0 * (embed_fwd_flops_per_token(cfg) + cfg.n_layers
                      * layer_fwd_flops_per_token(cfg, self.seq_len)
                      + head_fwd_flops_per_token(cfg)) * self.tokens

    def adapter_bytes(self, cut, adapter_bytes):
        return cut * self.cfg.lora_params_per_layer() * adapter_bytes

    def device_weight_bytes(self, cut, weight_bytes=2):
        return (self.cfg.vocab_size * self.cfg.d_model * weight_bytes
                + cut * self.cfg.params_per_layer() * weight_bytes)


@pytest.mark.parametrize("seed", [11, 2147600101, 77])
def test_uniform_stack_card_decisions_unchanged(seed):
    """qwen3-0.6b under the benchmark's Table II contexts (5 Table I
    devices, "normal" channel, 4 x 512 tokens, 5 local epochs, 40 rounds):
    per-layer pricing decides exactly as cut x one layer did."""
    from repro.core import card
    from repro.core.channel import SEED_STRIDE, WirelessChannel
    cfg = get_config("qwen3-0.6b")
    sim = SimParams(mini_batch=4, seq_len=512, local_epochs=5)
    new, old = Workload(cfg, 4, 512), _OneLayerTimesCut(cfg, 4, 512)
    for c in range(cfg.n_layers + 1):
        assert new.device_flops(c) == old.device_flops(c)
        assert new.adapter_bytes(c, 4) == old.adapter_bytes(c, 4)
        assert new.device_weight_bytes(c) == old.device_weight_bytes(c)
    assert new.total_flops() == old.total_flops()
    chans = [WirelessChannel("normal", seed=seed + SEED_STRIDE * m)
             for m in range(5)]
    for _ in range(40):
        for m, ch in enumerate(chans):
            state = ch.draw()
            got, want = (card.card(RoundContext(
                workload=w, device=EDGE_FLEET[m], server=SERVER_RTX4060TI,
                channel=state, sim=sim)) for w in (new, old))
            assert (got.cut, got.frequency, got.cost) == \
                (want.cut, want.frequency, want.cost)
