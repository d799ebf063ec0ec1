"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the protocol code
    reshapes sharded share buffers freely (``CoalescingComm.flush``), which
    the ``Explicit`` axes ``make_mesh`` defaults to would refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mpc_mesh_shape(n_devices: Optional[int] = None) -> Tuple[int, int]:
    """(party, data) axis sizes for an MPC mesh on ``n_devices`` chips.

    The party axis is always 2 (two non-colluding servers); the data axis
    takes half the topology (rounded down to use device pairs), so any
    even-sized slice works — 512 chips gives the paper's (2, 256), 8
    host devices give (2, 4) — instead of the historical hard-coded
    (2, 256) that failed on everything but exactly 512 devices.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    if n_devices < 2:
        raise ValueError(
            f"MPC serving needs >= 2 devices (one per party), got "
            f"{n_devices}; use make_mpc_smoke_mesh() for 1-device CPU runs")
    return (2, n_devices // 2)


def make_mpc_mesh(n_data: Optional[int] = None):
    """MPC serving mesh: party = pod (2 non-colluding servers, each a
    slice used as ``n_data``-way data parallelism over the request batch).
    ``n_data`` defaults to ``jax.device_count() // 2`` (the paper's 512-chip
    topology yields 2 x 256)."""
    if n_data is None:
        _, n_data = mpc_mesh_shape()
    return _auto_mesh((2, n_data), ("party", "data"),
                      devices=jax.devices()[: 2 * n_data])


def make_mpc_smoke_mesh():
    """1-device MPC mesh with the serving axis names (CPU smoke tests:
    both party shards land on the same device, shardings still resolve,
    and the mesh-native serve path degenerates to local exchanges)."""
    return _auto_mesh((1, 1), ("party", "data"))


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))
