"""Hierarchical multi-server CARD: assignment optimality, scalar-vs-array
decision equivalence, and the S=1 degenerate case collapsing to the
paper's single-server CARD."""
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import card as C
from repro.core.channel import draw_channel_matrix
from repro.core.cost_model import (RoundContext, TieredRoundContext,
                                   Workload)
from repro.core.hardware import (DEFAULT_SIM, EDGE_FLEET, SERVER_RTX4060TI,
                                 ServerTier, make_heterogeneous_fleet,
                                 make_server_tier, tier_arrays)
from repro.core.scheduler import simulate_hierarchical_fleet


def _tctx(n_devices=4, n_servers=2, rounds=3, *, capacity=None, seed=1,
          tier_seed=7, state="normal", arch="llama32-1b"):
    cfg = get_config(arch)
    sim = DEFAULT_SIM
    wl = Workload(cfg, sim.mini_batch, sim.seq_len)
    devices = (EDGE_FLEET * 2)[:n_devices] if n_devices <= 10 \
        else make_heterogeneous_fleet(n_devices, seed=seed)
    tier = make_server_tier(n_servers, capacity=capacity or n_devices,
                            seed=tier_seed)
    ch = draw_channel_matrix(state, rounds, len(devices), seed=seed,
                             bandwidth_hz=sim.bandwidth_hz)
    return wl, devices, tier, ch, sim, \
        TieredRoundContext.build(wl, devices, tier, ch, sim)


# --- ServerTier --------------------------------------------------------------


def test_server_tier_validation():
    with pytest.raises(ValueError):
        ServerTier(servers=(), capacity=(), backhaul_bits_per_s=())
    with pytest.raises(ValueError):
        ServerTier(servers=(SERVER_RTX4060TI,), capacity=(1, 2),
                   backhaul_bits_per_s=(1e9,))
    with pytest.raises(ValueError):
        ServerTier(servers=(SERVER_RTX4060TI,), capacity=(0,),
                   backhaul_bits_per_s=(1e9,))
    with pytest.raises(ValueError):
        ServerTier(servers=(SERVER_RTX4060TI,), capacity=(1,),
                   backhaul_bits_per_s=(0.0,))


def test_make_server_tier_heterogeneous():
    tier = make_server_tier(4, seed=0)
    assert tier.n_servers == 4 and tier.total_capacity == 4000
    f = [s.f_max for s in tier.servers]
    assert len(set(f)) == 4, "jittered clocks must be distinct"
    arrs = tier_arrays(tier)
    assert arrs["f_max"].shape == (4,)
    assert (arrs["backhaul_bits_per_s"] > 0).all()


# --- a one-server tier vs the scalar single-server CARD ----------------------


def test_s1_tier_matches_flat_batched_context():
    """A 1-server tier is exactly the paper's single-server problem: its
    grid must give the float64 scalar ``card``'s cut on every (round,
    device) lane, with frequencies, costs, delays and energies to float32
    resolution (decisions, not roundoff, are the contract)."""
    cfg = get_config("llama32-1b")
    sim = DEFAULT_SIM
    wl = Workload(cfg, sim.mini_batch, sim.seq_len)
    devices = EDGE_FLEET[:4]
    ch = draw_channel_matrix("normal", 3, 4, seed=2,
                             bandwidth_hz=sim.bandwidth_hz)
    tier = ServerTier(servers=(SERVER_RTX4060TI,), capacity=(4,),
                      backhaul_bits_per_s=(1e9,))
    tctx = TieredRoundContext.build(wl, devices, tier, ch, sim)
    h = C.hierarchical_card(tctx)
    grid = C.tiered_card_grid(tctx)
    assert (h.assignment == 0).all()
    assert grid.cuts.shape == (1, 3, 4)
    # the assignment readout of one server is that server's lane
    for field in ("cuts", "freqs", "costs", "delays", "energies", "d_server"):
        np.testing.assert_array_equal(np.asarray(getattr(h, field)),
                                      np.asarray(getattr(grid, field))[0])
    for r in range(3):
        for m, dev in enumerate(devices):
            d = C.card(RoundContext(workload=wl, device=dev,
                                    server=SERVER_RTX4060TI,
                                    channel=ch.state(r, m), sim=sim))
            assert int(grid.cuts[0, r, m]) == d.cut, (r, m)
            np.testing.assert_allclose(float(grid.freqs[0, r, m]),
                                       d.frequency, rtol=1e-5)
            np.testing.assert_allclose(float(grid.costs[0, r, m]), d.cost,
                                       rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(float(grid.delays[0, r, m]), d.delay,
                                       rtol=1e-4)
            np.testing.assert_allclose(float(grid.energies[0, r, m]),
                                       d.energy, rtol=1e-4, atol=1e-6)


def test_tiered_grid_shape_and_masking():
    _, _, tier, _, _, tctx = _tctx(n_devices=5, n_servers=3, rounds=2)
    assert tctx.shape == (3, 2, 5)
    grid = C.tiered_card_grid(tctx)
    assert grid.cuts.shape == (3, 2, 5)
    mask = np.zeros((3, 5), bool)
    mask[0, :2] = True
    masked = np.asarray(tctx.mask_unassigned(grid.delays, mask))
    assert np.isnan(masked[1]).all() and np.isnan(masked[0, :, 2:]).all()
    assert np.isfinite(masked[0, :, :2]).all()


def test_aggregation_delay_counts_assigned_adapters():
    _, _, tier, _, sim, tctx = _tctx(n_devices=4, n_servers=2, rounds=2)
    cuts = np.full((2, 4), 3, np.int32)
    mask = np.zeros((2, 4), bool)
    mask[0] = [True, True, False, False]
    mask[1] = [False, False, True, True]
    agg = np.asarray(tctx.aggregation_delay(mask, cuts))
    assert agg.shape == (2, 2)
    bits = float(np.asarray(tctx.adapter_bits)[3])
    for s in range(2):
        expect = 2 * bits / float(np.asarray(tctx.backhaul_bits_per_s)[s])
        np.testing.assert_allclose(agg[s], expect, rtol=1e-6)


# --- assignment --------------------------------------------------------------


def test_assign_greedy_unconstrained_is_argmin():
    rng = np.random.default_rng(0)
    cost = rng.uniform(1, 2, size=(3, 10))
    a = C.assign_devices(cost, np.array([10, 10, 10]), method="greedy")
    np.testing.assert_array_equal(a, cost.argmin(axis=0))


def test_assign_optimal_matches_exhaustive_random_instances():
    rng = np.random.default_rng(42)
    for trial in range(20):
        n_s, n_d = 2, int(rng.integers(2, 7))
        cost = rng.uniform(1, 5, size=(n_s, n_d))
        cap = rng.integers(1, n_d, size=n_s)
        while cap.sum() < n_d:
            cap[rng.integers(n_s)] += 1
        a = C.assign_devices(cost, cap, method="optimal")
        e = C.exhaustive_assignment(cost, cap)
        idx = np.arange(n_d)
        np.testing.assert_allclose(cost[a, idx].sum(), cost[e, idx].sum(),
                                   rtol=1e-12,
                                   err_msg=f"trial {trial}: {a} vs {e}")
        assert (np.bincount(a, minlength=n_s) <= cap).all()


def test_assign_capacity_respected_and_infeasible_raises():
    cost = np.ones((2, 4))
    with pytest.raises(ValueError):
        C.assign_devices(cost, np.array([1, 1]), method="greedy")
    a = C.assign_devices(cost, np.array([2, 2]), method="optimal")
    assert (np.bincount(a, minlength=2) <= 2).all()
    with pytest.raises(ValueError):
        C.assign_devices(cost, np.array([2, 2]), method="nope")


# --- hierarchical_card -------------------------------------------------------


def test_hierarchical_matches_exhaustive_small_fleets():
    """Acceptance: decisions match exhaustive assignment enumeration on
    fleets <= 8 devices x 2 servers."""
    for n_d, cap, seed in ((4, 3, 7), (6, 4, 11), (8, 5, 3)):
        _, _, tier, _, _, tctx = _tctx(n_devices=n_d, n_servers=2,
                                       capacity=cap, tier_seed=seed)
        h = C.hierarchical_card(tctx, assign="optimal")
        e = C.hierarchical_card_exhaustive(tctx)
        np.testing.assert_array_equal(h.assignment, e.assignment)
        np.testing.assert_array_equal(h.cuts, e.cuts)
        np.testing.assert_array_equal(h.freqs, e.freqs)
        np.testing.assert_array_equal(h.aggregation_s, e.aggregation_s)


def test_hierarchical_scalar_vs_batched_equivalence():
    """The float64 scalar loop (RoundContext + card per cell) and the jitted
    (S, R, D, C) grid agree on every decision."""
    wl, devices, tier, ch, sim, tctx = _tctx(n_devices=5, n_servers=2,
                                             capacity=3, rounds=3)
    hb = C.hierarchical_card(tctx, assign="optimal")
    hs = C.hierarchical_card_scalar(wl, devices, tier, ch, sim,
                                    assign="optimal")
    np.testing.assert_array_equal(hb.assignment, hs.assignment)
    np.testing.assert_array_equal(hb.cuts, hs.cuts)
    np.testing.assert_allclose(hb.freqs, hs.freqs, rtol=1e-5)
    np.testing.assert_allclose(hb.delays, hs.delays, rtol=1e-5)
    np.testing.assert_allclose(hb.energies, hs.energies, rtol=1e-4)
    np.testing.assert_allclose(hb.aggregation_s, hs.aggregation_s, rtol=1e-5)


def test_greedy_equals_optimal_with_slack_capacity():
    _, _, _, _, _, tctx = _tctx(n_devices=6, n_servers=3, capacity=6)
    g = C.hierarchical_card(tctx, assign="greedy")
    o = C.hierarchical_card(tctx, assign="optimal")
    np.testing.assert_array_equal(g.assignment, o.assignment)


def test_capacity_binds_load():
    _, _, tier, _, _, tctx = _tctx(n_devices=6, n_servers=2, capacity=3)
    h = C.hierarchical_card(tctx, assign="optimal")
    assert (h.server_load <= 3).all() and h.server_load.sum() == 6


# --- simulate_hierarchical_fleet --------------------------------------------


def test_simulate_hierarchical_fleet_round_times():
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(12, seed=3)
    tier = make_server_tier(3, capacity=6, seed=2)
    log = simulate_hierarchical_fleet(cfg, tier=tier, rounds=4,
                                      devices=fleet, seed=5)
    assert log.round_s.shape == (4,)
    assert log.server_round_s.shape == (3, 4)
    # the fleet round closes with the slowest server (incl. backhaul push)
    np.testing.assert_allclose(log.round_s, log.server_round_s.max(axis=0))
    assert np.isfinite(log.mean_round_s())
    assert log.decision.server_load.sum() == 12
    # more servers can only help (or tie) the mean round time
    tier1 = make_server_tier(1, capacity=12, seed=2)
    log1 = simulate_hierarchical_fleet(cfg, tier=tier1, rounds=4,
                                       devices=fleet, seed=5)
    assert log.mean_round_s() <= log1.mean_round_s() * 1.05
