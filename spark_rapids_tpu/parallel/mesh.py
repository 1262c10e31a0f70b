"""Device-mesh management — the TPU analog of GpuDeviceManager's device
acquisition (reference GpuDeviceManager.scala:115 setGpuDeviceAndAcquire).

Instead of binding one CUDA device per executor, the engine builds a
jax.sharding.Mesh over the chips this host can see. Single-host Spark
executors pin 1 task slice per chip (DP over the 'data' axis); multi-host
pods extend the same mesh over ICI with jax's distributed runtime.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"


def shard_map_compat(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off (exchange bodies
    intentionally produce per-shard-distinct outputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def device_mesh(n_devices: Optional[int] = None,
                axis_name: str = DATA_AXIS) -> Mesh:
    """1-D mesh over the first n visible devices (default: all). Shuffle
    exchanges ride this axis as all-to-all collectives."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(f"a mesh of {n_devices} devices was asked for; "
                             f"{len(devs)} are visible")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def mesh_axis_size(mesh: Mesh, axis_name: str = DATA_AXIS) -> int:
    return mesh.shape[axis_name]


# -- active mesh (planner seam) ---------------------------------------------
# The session installs its mesh here; TpuOverrides reads it to decide
# whether to plan distributed stages (partial → exchange → final, shuffled
# joins). The analog of the reference's "is a shuffle manager configured"
# check (RapidsShuffleInternalManagerBase).

_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH
