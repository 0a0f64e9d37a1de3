"""Mesh and ``shard_map`` helpers in the installed jax's spellings.

The repo targets jax >= 0.9 (``jax.shard_map`` with ``check_vma``,
``jax.sharding.AxisType``). Mesh construction goes through here so
every caller builds meshes with the same axis types.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

_AUTO = jax.sharding.AxisType.Auto


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check disabled.

    Required when the mapped body carries state through ``lax.scan``
    that the check cannot type (the simulator's round carry) or
    contains a ``pallas_call``, which has no replication rule.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(_AUTO,) * len(axis_names))


def make_mesh_1d(n_devices: int, axis_name: str):
    """A 1-D mesh over the first ``n_devices`` local devices.

    Unlike :func:`make_mesh` this slices the device list explicitly, so
    sweeps can shard over a subset of the host's devices.
    """
    devs = np.asarray(jax.devices()[:n_devices])
    return jax.sharding.Mesh(devs, (axis_name,), axis_types=(_AUTO,))
