"""Measured cost model: roofline fit, latency tables, and the guarantee
that the "measured" backend is a strict generalization of the analytic one.

The load-bearing property: a ``LatencyTable`` synthesized *from* the
analytic model (``from_analytic``) must reproduce the analytic
``card``/``tiered_card_grid`` decisions exactly — same cuts, same Eq. 16
frequencies, same delays/energies — across architectures, channel states,
and both fleet engines. Everything the measured path changes is then
attributable to the calibration, not to the plumbing.
"""
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.cost_model import (RoundContext, TieredRoundContext,
                                   Workload, resolve_compute)
from repro.core.hardware import (DEFAULT_SIM, EDGE_FLEET, SERVER_RTX4060TI,
                                 ServerTier, profile_from_throughput)
from repro.core.measured_cost import (LatencyTable, ProbeResult, RooflineFit,
                                      TableCompute, build_latency_tables,
                                      fit_roofline)
from repro.core.scheduler import simulate_fleet

ARCHS = ("llama32-1b", "qwen3-4b", "granite-moe-3b-a800m")
STATES = ("good", "normal", "poor")

BATCH, SEQ = DEFAULT_SIM.mini_batch, DEFAULT_SIM.seq_len


def _analytic_table(arch):
    return LatencyTable.from_analytic(
        Workload(get_config(arch), BATCH, SEQ))


def _synthetic_fit(backend="jnp"):
    """A plausible edge-host roofline, no probing needed."""
    return RooflineFit(inv_compute=1e-11, inv_bandwidth=2e-11,
                       overhead_s=1e-4, achieved_flops_per_s=8e10,
                       rel_residual=0.05, n_probes=8, backend=backend)


def _assert_logs_match(a, b):
    assert np.array_equal(a.cuts, b.cuts)
    np.testing.assert_allclose(a.freqs, b.freqs, rtol=1e-5)
    np.testing.assert_allclose(a.delays, b.delays, rtol=1e-4)
    np.testing.assert_allclose(a.energies, b.energies, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Analytic/measured equivalence — the acceptance bar
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("arch", ARCHS)
def test_measured_reproduces_analytic_decisions(arch, state):
    """The array grid on an analytic-synthesized table == pure analytic."""
    cfg = get_config(arch)
    kw = dict(channel_state=state, rounds=5, seed=3, respect_memory=False)
    a = simulate_fleet(cfg, **kw)
    m = simulate_fleet(cfg, cost_source="measured",
                       latency_table=_analytic_table(arch), **kw)
    _assert_logs_match(a, m)


def test_measured_reproduces_analytic_scalar_engine():
    """Same equivalence through the scalar oracle (RoundContext + card)."""
    cfg = get_config("llama32-1b")
    kw = dict(channel_state="normal", rounds=4, seed=5, engine="scalar",
              respect_memory=False)
    a = simulate_fleet(cfg, **kw)
    m = simulate_fleet(cfg, cost_source="measured",
                       latency_table=_analytic_table("llama32-1b"), **kw)
    _assert_logs_match(a, m)


def test_analytic_table_flops_match_workload_exactly():
    """TableCompute on a from_analytic table is bit-for-bit the Workload
    accounting at every cut — not just decision-equivalent."""
    for arch in ARCHS:
        cfg = get_config(arch)
        w = Workload(cfg, BATCH, SEQ)
        tc = resolve_compute(w, "measured", _analytic_table(arch))
        for cut in range(cfg.n_layers + 1):
            assert tc.device_flops(cut) == pytest.approx(
                w.device_flops(cut), rel=1e-12)
            assert tc.server_flops(cut) == pytest.approx(
                w.server_flops(cut), rel=1e-9)
        assert tc.total_flops() == pytest.approx(w.total_flops(), rel=1e-12)


def test_measured_batched_card_end_to_end():
    """A calibrated (synthetic-fit) table runs through the array grid and
    produces sane decisions: valid cuts, clipped frequencies, finite costs."""
    cfg = get_config("llama32-1b")
    table = LatencyTable.from_fit(cfg, _synthetic_fit(), batch=BATCH,
                                  seq_len=SEQ)
    log = simulate_fleet(cfg, cost_source="measured", latency_table=table,
                         rounds=4, seed=1, respect_memory=False)
    assert ((log.cuts >= 0) & (log.cuts <= cfg.n_layers)).all()
    for m, dev in enumerate(EDGE_FLEET):
        assert (log.freqs[:, m] <= dev.f_max * (1 + 1e-6)).all()
    assert np.isfinite(log.delays).all() and (log.delays > 0).all()
    assert np.isfinite(log.energies).all()


def test_round_context_measured_vs_analytic_costs():
    """Per-cut objective sweep: measured-with-analytic-table == analytic."""
    cfg = get_config("qwen3-4b")
    w = Workload(cfg, BATCH, SEQ)
    from repro.core.channel import WirelessChannel
    ch = WirelessChannel("normal", seed=2).draw()
    base = dict(workload=w, device=EDGE_FLEET[2], server=SERVER_RTX4060TI,
                channel=ch, sim=DEFAULT_SIM)
    ctx_a = RoundContext(**base)
    ctx_m = RoundContext(cost_source="measured",
                         latency_table=_analytic_table("qwen3-4b"), **base)
    f = SERVER_RTX4060TI.f_max
    for cut in (0, cfg.n_layers // 2, cfg.n_layers):
        assert ctx_m.device_comp_delay(cut) == pytest.approx(
            ctx_a.device_comp_delay(cut), rel=1e-12)
        assert ctx_m.server_comp_delay(cut, f) == pytest.approx(
            ctx_a.server_comp_delay(cut, f), rel=1e-9)


def test_batched_context_build_measured():
    w = Workload(get_config("llama32-1b"), BATCH, SEQ)
    from repro.core.channel import draw_channel_matrix
    chans = draw_channel_matrix("normal", 2, len(EDGE_FLEET), seed=0)
    tier = ServerTier(servers=(SERVER_RTX4060TI,),
                      capacity=(len(EDGE_FLEET),), backhaul_bits_per_s=(1e9,))
    b_a = TieredRoundContext.build(w, EDGE_FLEET, tier, chans, DEFAULT_SIM)
    b_m = TieredRoundContext.build(w, EDGE_FLEET, tier, chans, DEFAULT_SIM,
                                   cost_source="measured",
                                   latency_table=_analytic_table(
                                       "llama32-1b"))
    np.testing.assert_allclose(np.asarray(b_m.dev_flops),
                               np.asarray(b_a.dev_flops), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b_m.srv_flops),
                               np.asarray(b_a.srv_flops), rtol=1e-6)


# ---------------------------------------------------------------------------
# Roofline fit
# ---------------------------------------------------------------------------


def _probes_from_model(t0, inv_c, inv_b, backend="jnp"):
    shapes = [(1e6, 1e5), (1e7, 1e6), (1e8, 1e7), (1e9, 5e7),
              (5e9, 1e8), (2e10, 4e8), (1e6, 1e8), (1e7, 5e8)]
    return [ProbeResult(kernel="synthetic", backend=backend, shape=f"p{i}",
                        flops=f, hbm_bytes=by,
                        seconds=t0 + f * inv_c + by * inv_b)
            for i, (f, by) in enumerate(shapes)]


def test_fit_recovers_known_roofline():
    t0, inv_c, inv_b = 2e-4, 1e-11, 5e-12
    fit = fit_roofline(_probes_from_model(t0, inv_c, inv_b))
    assert fit.overhead_s == pytest.approx(t0, rel=1e-6)
    assert fit.inv_compute == pytest.approx(inv_c, rel=1e-6)
    assert fit.inv_bandwidth == pytest.approx(inv_b, rel=1e-6)
    assert fit.ref_throughput == pytest.approx(1.0 / inv_c, rel=1e-6)
    assert fit.rel_residual < 1e-6
    # predictions reproduce the generating model
    assert fit.predict(1e9, 1e7) == pytest.approx(
        t0 + 1e9 * inv_c + 1e7 * inv_b, rel=1e-6)


def test_fit_nnls_clips_to_nonnegative():
    """Compute-only data must not produce a negative bandwidth slope."""
    fit = fit_roofline(_probes_from_model(1e-4, 1e-11, 0.0))
    assert fit.inv_bandwidth >= 0.0
    assert fit.inv_compute > 0.0
    assert fit.overhead_s >= 0.0


def test_fit_bandwidth_bound_host_has_finite_currency():
    """When compute never binds, ref_throughput falls back to the achieved
    rate — LatencyTable construction must stay finite."""
    fit = fit_roofline(_probes_from_model(0.0, 0.0, 1e-11))
    assert fit.inv_compute == 0.0
    assert np.isfinite(fit.ref_throughput) and fit.ref_throughput > 0
    table = LatencyTable.from_fit(get_config("llama32-1b"), fit,
                                  batch=BATCH, seq_len=SEQ)
    assert np.isfinite(table.ref_throughput)


def test_fit_requires_probes():
    with pytest.raises(ValueError):
        fit_roofline([])


# ---------------------------------------------------------------------------
# LatencyTable / TableCompute validation and serialization
# ---------------------------------------------------------------------------


def test_latency_table_roundtrip():
    table = LatencyTable.from_fit(get_config("qwen3-4b"), _synthetic_fit(),
                                  batch=BATCH, seq_len=SEQ)
    again = LatencyTable.from_dict(table.to_dict())
    assert again == table
    fit = _synthetic_fit()
    assert RooflineFit.from_dict(fit.to_dict()) == fit


def test_latency_table_rejects_bad_schema_and_values():
    d = _analytic_table("llama32-1b").to_dict()
    d["schema"] = "nonsense/v9"
    with pytest.raises(ValueError):
        LatencyTable.from_dict(d)
    good = _analytic_table("llama32-1b")
    with pytest.raises(ValueError):
        LatencyTable(arch=good.arch, batch=good.batch, seq_len=good.seq_len,
                     ref_throughput=0.0, embed_s=good.embed_s,
                     layer_s=good.layer_s, head_s=good.head_s)
    with pytest.raises(ValueError):
        LatencyTable(arch=good.arch, batch=good.batch, seq_len=good.seq_len,
                     ref_throughput=1.0, embed_s=good.embed_s,
                     layer_s=(-1.0,) * good.n_layers, head_s=good.head_s)


def test_table_compute_validates_workload_match():
    w = Workload(get_config("llama32-1b"), BATCH, SEQ)
    with pytest.raises(ValueError):  # wrong architecture
        TableCompute(workload=w, table=_analytic_table("qwen3-4b"))
    with pytest.raises(ValueError):  # wrong measurement shape
        TableCompute(workload=Workload(get_config("llama32-1b"), 8, 256),
                     table=_analytic_table("llama32-1b"))
    good = _analytic_table("llama32-1b")
    with pytest.raises(ValueError):  # wrong depth
        TableCompute(workload=w, table=LatencyTable(
            arch=good.arch, batch=good.batch, seq_len=good.seq_len,
            ref_throughput=1.0, embed_s=good.embed_s,
            layer_s=good.layer_s[:-1], head_s=good.head_s))


def test_resolve_compute_errors():
    w = Workload(get_config("llama32-1b"), BATCH, SEQ)
    with pytest.raises(ValueError):
        resolve_compute(w, "measured")          # needs a table
    with pytest.raises(ValueError):
        resolve_compute(w, "vibes")             # unknown source


def test_build_latency_tables_covers_archs():
    tables = build_latency_tables(_synthetic_fit(), batch=BATCH, seq_len=SEQ,
                                  archs=ARCHS)
    assert set(tables) == set(ARCHS)
    for arch, t in tables.items():
        assert t.arch == arch
        assert t.n_layers == get_config(arch).n_layers
        assert t.source == "measured:jnp"


def test_profile_from_throughput():
    prof = profile_from_throughput("bench-host", 1.23e11)
    assert prof.delta * prof.f_max == pytest.approx(1.23e11)
