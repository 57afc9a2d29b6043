"""Vectorized CARD fleet engine vs the scalar reference oracle.

The array path must be a pure refactor of the decision layer: identical
channel realizations in, identical (cut, frequency) decisions out, for every
policy, architecture, and channel state — plus the new exact parallel-SL
round time must land inside the legacy upper/lower bounds.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core import card as C
from repro.core.channel import (SEED_STRIDE, WirelessChannel,
                                draw_channel_matrix)
from repro.core.cost_model import RoundContext, TieredRoundContext, Workload
from repro.core.hardware import (DEFAULT_SIM, EDGE_FLEET, SERVER_RTX4060TI,
                                 ServerTier, SimParams,
                                 make_heterogeneous_fleet)
from repro.core.scheduler import parallel_round_stats, simulate_fleet

ARCHS = ("llama32-1b", "qwen3-4b", "granite-moe-3b-a800m")
STATES = ("good", "normal", "poor")


def _one_server_ctx(workload, devices, batch, sim):
    """The paper's single edge server as a one-server tier."""
    tier = ServerTier(servers=(SERVER_RTX4060TI,), capacity=(len(devices),),
                      backhaul_bits_per_s=(1e9,))
    return TieredRoundContext.build(workload, devices, tier, batch, sim)


def _assert_logs_match(a, b):
    assert np.array_equal(a.cuts, b.cuts)
    np.testing.assert_allclose(a.freqs, b.freqs, rtol=1e-5)
    np.testing.assert_allclose(a.delays, b.delays, rtol=1e-4)
    np.testing.assert_allclose(a.energies, b.energies, rtol=1e-4, atol=1e-6)
    for k in ("d_device", "d_uplink", "d_server", "d_downlink"):
        np.testing.assert_allclose(getattr(a, k), getattr(b, k), rtol=1e-4,
                                   atol=1e-9)


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("arch", ARCHS)
def test_card_engines_equivalent(arch, state):
    """The acceptance bar: same (cut, f) decisions, 3 archs x 3 states."""
    cfg = get_config(arch)
    a = simulate_fleet(cfg, channel_state=state, rounds=6, seed=7,
                       engine="scalar")
    b = simulate_fleet(cfg, channel_state=state, rounds=6, seed=7,
                       engine="vectorized")
    _assert_logs_match(a, b)


@pytest.mark.parametrize("policy", ["server_only", "device_only", "static",
                                    "random"])
def test_baseline_engines_equivalent(policy):
    cfg = get_config("llama32-1b")
    kw = dict(policy=policy, rounds=4, seed=11,
              static_cut=9 if policy == "static" else None)
    _assert_logs_match(simulate_fleet(cfg, engine="scalar", **kw),
                       simulate_fleet(cfg, engine="vectorized", **kw))


# derandomize: the engines agree to float32 resolution, so a fresh random
# fleet each CI run could in principle hit a near-tied cost and flake;
# a fixed example sequence keeps the decision-identity check deterministic
@settings(max_examples=10, deadline=None, derandomize=True)
@given(n_devices=st.integers(2, 24), seed=st.integers(0, 999),
       state=st.sampled_from(STATES))
def test_batched_card_matches_scalar_on_random_fleets(n_devices, seed, state):
    """Property: decision-for-decision match on randomized heterogeneous
    fleets (platform mix and clock jitter drawn from the seed)."""
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(n_devices, seed=seed)
    a = simulate_fleet(cfg, channel_state=state, rounds=3, seed=seed,
                       devices=fleet, engine="scalar")
    b = simulate_fleet(cfg, channel_state=state, rounds=3, seed=seed,
                       devices=fleet, engine="vectorized")
    _assert_logs_match(a, b)


def test_channel_matrix_matches_sequential_draws():
    """Batched sampling consumes the per-device PRNG streams in the exact
    order of sequential scalar draw() calls."""
    sim = DEFAULT_SIM
    batch = draw_channel_matrix("normal", 5, 3, seed=2,
                                bandwidth_hz=sim.bandwidth_hz)
    for m in range(3):
        ch = WirelessChannel("normal", seed=2 + SEED_STRIDE * m,
                             bandwidth_hz=sim.bandwidth_hz)
        for r in range(5):
            s = ch.draw()
            assert batch.snr_up_db[r, m] == pytest.approx(s.snr_up_db)
            assert batch.snr_down_db[r, m] == pytest.approx(s.snr_down_db)
            assert batch.state(r, m).rate_up == pytest.approx(s.rate_up)


def test_delay_components_sum_to_round_delay():
    cfg = get_config("llama32-1b")
    sim = DEFAULT_SIM
    ctx = RoundContext(workload=Workload(cfg, sim.mini_batch, sim.seq_len),
                       device=EDGE_FLEET[1], server=SERVER_RTX4060TI,
                       channel=WirelessChannel("normal", seed=4).draw(),
                       sim=sim)
    for cut in (0, 7, cfg.n_layers):
        f = C.optimal_frequency(ctx)
        parts = ctx.delay_components(cut, f)
        assert parts.total == pytest.approx(ctx.round_delay(cut, f), rel=1e-12)


def test_parallel_exact_within_legacy_bounds():
    cfg = get_config("llama32-1b")
    for state in STATES:
        log = simulate_fleet(cfg, channel_state=state, rounds=8, seed=3)
        s = parallel_round_stats(log)
        assert (s["parallel_lower_s"] - 1e-9 <= s["parallel_exact_s"]
                <= s["parallel_upper_s"] + 1e-9), (state, s)
        # exact sequential time is the component sum too
        comp_sum = (log.d_device + log.d_uplink + log.d_server
                    + log.d_downlink)
        np.testing.assert_allclose(comp_sum, log.delays, rtol=1e-6)


def test_batched_card_beats_joint_grid():
    """Closed-form f* + cut argmin must never lose to the vmapped (f, c)
    exhaustive grid (it can only tie or win, by Eq. 16 convexity)."""
    cfg = get_config("qwen3-4b")
    sim = DEFAULT_SIM
    batch = draw_channel_matrix("normal", 3, len(EDGE_FLEET), seed=5,
                                bandwidth_hz=sim.bandwidth_hz,
                                tx_power_dbm_up=sim.tx_power_dbm_up,
                                tx_power_dbm_down=sim.tx_power_dbm_down,
                                noise_dbm_per_hz=sim.noise_dbm_per_hz)
    tctx = _one_server_ctx(Workload(cfg, sim.mini_batch, sim.seq_len),
                           EDGE_FLEET, batch, sim)
    a = C.tiered_card_grid(tctx)
    g = C.tiered_card_joint_bruteforce(tctx, n_freq=60)
    assert a.cuts.shape == g.cuts.shape == (1, 3, len(EDGE_FLEET))
    assert np.all(np.asarray(a.costs) <= np.asarray(g.costs) + 1e-5)
    assert np.array_equal(np.asarray(a.cuts), np.asarray(g.cuts))


def test_memory_mask_batched_matches_scalar():
    """A 1T-param model can't fit any Jetson: every array decision must
    respect the same per-device feasibility cap the scalar path derives."""
    kimi = get_config("kimi-k2-1t-a32b")
    sim = SimParams(mini_batch=1, seq_len=128)
    w = Workload(kimi, 1, 128)
    batch = draw_channel_matrix("normal", 2, len(EDGE_FLEET), seed=0,
                                bandwidth_hz=sim.bandwidth_hz)
    tctx = _one_server_ctx(w, EDGE_FLEET, batch, sim)
    for m, dev in enumerate(EDGE_FLEET):
        ctx = RoundContext(workload=w, device=dev, server=SERVER_RTX4060TI,
                           channel=batch.state(0, m), sim=sim)
        assert int(tctx.max_cut[m]) == ctx.max_feasible_cut()
    dec = C.tiered_card_grid(tctx)
    assert np.all(np.asarray(dec.cuts) == 0)


def test_thousand_device_round_end_to_end():
    """Acceptance: a 1000-device heterogeneous round runs end-to-end."""
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(1000, seed=0)
    log = simulate_fleet(cfg, rounds=1, devices=fleet, seed=0)
    assert log.cuts.shape == (1, 1000)
    assert np.isfinite(log.delays).all() and np.isfinite(log.energies).all()
    assert (log.delays > 0).all()
    stats = parallel_round_stats(log)
    assert stats["parallel_exact_s"] >= stats["parallel_lower_s"] - 1e-9
