"""Multi-host bootstrap + ICI/DCN mesh topology (SURVEY §2.10 mapping:
"ICI intra-slice + host-staged inter-slice"; the reference's analog is the
executor-side distributed init in Plugin.scala plus the UCX/netty split
between fast P2P and the always-works host plane).

Two pieces:

1. `initialize_distributed()` — jax.distributed bootstrap from standard
   cluster env (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID, or the
   TPU pod metadata jax discovers natively). Idempotent; a no-op for
   single-process runs so the same engine code runs everywhere.

2. `build_query_mesh(devices)` — a 2-D ("dcn", "ici") Mesh: the inner
   axis spans each host's local devices (ICI — all-to-all shuffle
   exchanges ride it, parallel/exchange.py), the outer axis spans hosts
   (DCN — only partial→final aggregation trees and broadcasts cross it).
   Exchange planning keys on the ICI axis size, so shuffles NEVER cross
   DCN implicitly: inter-host movement goes through the host shuffle
   plane (shuffle/manager.py), mirroring the reference's UCX-fast-path /
   file-shuffle-fallback split.
"""

from __future__ import annotations

import logging
import os
from typing import List, Optional, Sequence, Tuple

log = logging.getLogger("spark_rapids_tpu.multihost")

DCN_AXIS = "dcn"
ICI_AXIS = "data"  # same name the single-host mesh uses (mesh.py)

_initialized = False


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """jax.distributed.initialize from args or environment. Returns True
    when a multi-process runtime was brought up, False for single-process
    (both are valid engine states). Idempotent."""
    global _initialized
    if _initialized:
        return True
    coordinator = coordinator or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes or _int_env("NUM_PROCESSES")
    process_id = process_id if process_id is not None \
        else _int_env("PROCESS_ID")
    import jax
    if coordinator is None:
        # TPU pods: jax discovers the coordinator from metadata; only
        # attempt on a genuinely multi-HOST slice (single-host setups
        # export the var with one entry)
        hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES",
                                           "").split(",") if h]
        if len(hosts) > 1:
            jax.distributed.initialize()
            _initialized = True
            return True
        return False  # single-process (no coordinator configured)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    log.info("distributed runtime up: process %s of %s via %s",
             process_id, num_processes, coordinator)
    return True


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def group_devices_by_host(devices: Sequence) -> List[List]:
    """Stable grouping by process index (each jax process = one host)."""
    hosts: dict = {}
    for d in devices:
        hosts.setdefault(getattr(d, "process_index", 0), []).append(d)
    return [hosts[k] for k in sorted(hosts)]


def topology_shape(devices: Sequence) -> Tuple[int, int]:
    """(n_hosts, devices_per_host); raises on ragged topologies (a host
    down mid-allocation — fail fast, task retry is the recovery model)."""
    groups = group_devices_by_host(devices)
    per_host = {len(g) for g in groups}
    if len(per_host) != 1:
        raise RuntimeError(
            f"ragged device topology: {sorted(len(g) for g in groups)} "
            "devices per host — refusing to build a mesh")
    return len(groups), per_host.pop()


def build_query_mesh(devices: Optional[Sequence] = None):
    """('dcn', 'data') Mesh: inner axis = a host's local chips (ICI),
    outer = hosts (DCN). Single-host collapses to (1, n)."""
    import numpy as np

    import jax
    from jax.sharding import Mesh
    devices = list(devices) if devices is not None else jax.devices()
    n_hosts, per_host = topology_shape(devices)
    grid = np.empty((n_hosts, per_host), dtype=object)
    for hi, group in enumerate(group_devices_by_host(devices)):
        for di, d in enumerate(group):
            grid[hi, di] = d
    return Mesh(grid, (DCN_AXIS, ICI_AXIS))


def ici_axis_size(mesh) -> int:
    return mesh.shape[ICI_AXIS]


def dcn_axis_size(mesh) -> int:
    return mesh.shape.get(DCN_AXIS, 1) if hasattr(mesh.shape, "get") \
        else mesh.shape[DCN_AXIS]
