"""Sharded fleet sweeps: ``simulate_fleet(..., mesh=...)`` must be
bit-identical to the single-host vectorized engine — sharding the devices
axis changes data placement, never values.

The in-process tests use a 1-shard mesh (the test session pins one CPU
device); multi-shard meshes need ``--xla_force_host_platform_device_count``
set before jax initializes, so those run in a subprocess (same pattern as
``test_system.py``).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.scheduler import simulate_fleet
from repro.core.hardware import make_heterogeneous_fleet
from repro.launch.mesh import make_fleet_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

LOG_FIELDS = ("cuts", "freqs", "delays", "energies",
              "d_device", "d_uplink", "d_server", "d_downlink")


def _assert_identical(a, b):
    for f in LOG_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f} drifted")


@pytest.mark.parametrize("policy", ["card", "server_only", "random"])
def test_one_shard_mesh_bit_identical(policy):
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(32, seed=3)
    a = simulate_fleet(cfg, policy=policy, rounds=3, devices=fleet, seed=5)
    b = simulate_fleet(cfg, policy=policy, rounds=3, devices=fleet, seed=5,
                       mesh=make_fleet_mesh(1))
    _assert_identical(a, b)


def test_one_shard_mesh_1k_devices_bit_identical():
    """Acceptance: sharded == single-host at 1k devices."""
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(1000, seed=3)
    a = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet, seed=5)
    b = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet, seed=5,
                       mesh=make_fleet_mesh(1))
    _assert_identical(a, b)


def test_mesh_requires_vectorized_engine():
    cfg = get_config("llama32-1b")
    with pytest.raises(ValueError):
        simulate_fleet(cfg, rounds=1, engine="scalar",
                       mesh=make_fleet_mesh(1))


def test_pad_lanes_trimmed():
    """5 devices on a 1-shard mesh still pads cleanly (pad=0) and ragged
    fleets never leak pad lanes into the log."""
    cfg = get_config("llama32-1b")
    fleet = make_heterogeneous_fleet(5, seed=1)
    log = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet,
                         seed=2, mesh=make_fleet_mesh(1))
    assert log.delays.shape == (2, 5)
    assert np.isfinite(log.delays).all()


@pytest.mark.slow
def test_multi_shard_subprocess_bit_identical():
    """Acceptance: meshes of 1, 2, 4 shards at 1k devices, all bit-identical
    to the unsharded engine — including a ragged fleet that needs padding."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        from repro.configs.base import get_config
        from repro.core.scheduler import simulate_fleet
        from repro.core.hardware import make_heterogeneous_fleet
        from repro.launch.mesh import make_fleet_mesh

        fields = ("cuts", "freqs", "delays", "energies", "d_device",
                  "d_uplink", "d_server", "d_downlink")
        cfg = get_config("llama32-1b")
        fleet = make_heterogeneous_fleet(1000, seed=3)
        a = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet,
                           seed=5)
        for n in (1, 2, 4):
            b = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet,
                               seed=5, mesh=make_fleet_mesh(n))
            assert all(np.array_equal(getattr(a, f), getattr(b, f))
                       for f in fields), f"{n} shards drifted"
        # ragged: 10 devices on 4 shards pads 2 dummy lanes
        fleet10 = make_heterogeneous_fleet(10, seed=9)
        a10 = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet10,
                             seed=1)
        b10 = simulate_fleet(cfg, policy="card", rounds=2, devices=fleet10,
                             seed=1, mesh=make_fleet_mesh(4))
        assert all(np.array_equal(getattr(a10, f), getattr(b10, f))
                   for f in fields), "ragged padding drifted"
        print("SHARDED-OK")
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    timeout_s = 560.0
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout_s,
                       env=env)
    assert "SHARDED-OK" in r.stdout, r.stderr[-2000:]
