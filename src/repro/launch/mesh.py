"""Production meshes.

Single pod: 16 x 16 = 256 TPU-v5e chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is data-parallel across pods (each pod serves one group of edge
devices in the SL deployment).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.

Every mesh here uses ``AxisType.Auto`` axes: the models place arrays with
``PartitionSpec`` constraints and leave the rest to GSPMD propagation, which
``jax.make_mesh``'s default of explicit axes would reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """A CPU-sized mesh for tests."""
    return _auto_mesh(shape, axes)


def make_fleet_mesh(n_shards: int = 0):
    """A 1-D ``("data",)`` mesh that shards the *devices* axis of a fleet
    sweep (``simulate_fleet(..., mesh=...)``). ``n_shards=0`` uses every
    host device. Distinct from the 2-D model meshes above: fleet sweeps
    have no model axis — each lane is one edge device's decision problem.
    """
    if n_shards <= 0:
        n_shards = len(jax.devices())
    return _auto_mesh((n_shards,), ("data",))
