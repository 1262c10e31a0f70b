"""Device manager — plugin-lifecycle device/mesh acquisition (reference
GpuDeviceManager.scala:115 setGpuDeviceAndAcquire, :150
initializeGpuAndMemory). On TPU the 'device' is a jax device (one chip per
executor, the SURVEY §2.10 pinning model) or a Mesh over many for the ICI
shuffle/collective path."""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .budget import memory_budget, reset_memory_budget
from .semaphore import reset_tpu_semaphore


class DeviceManager:
    def __init__(self):
        self.initialized = False
        self.device = None
        self.mesh = None
        self._lock = threading.Lock()

    def initialize(self, device_ordinal: int = 0,
                   mesh_axes: Optional[dict] = None):
        """Executor init (reference Plugin.scala:484 RapidsExecutorPlugin):
        pick the chip, size the HBM budget, arm the admission semaphore,
        optionally build the pod mesh."""
        with self._lock:
            if self.initialized:
                return self
            devices = jax.devices()
            if not 0 <= device_ordinal < len(devices):
                raise ValueError(
                    f"device ordinal {device_ordinal} out of range: "
                    f"{len(devices)} device(s) visible")
            self.device = devices[device_ordinal]
            memory_budget()  # force budget sizing against this device
            reset_tpu_semaphore()
            if mesh_axes:
                from ..parallel.mesh import build_mesh
                self.mesh = build_mesh(**mesh_axes)
            self.initialized = True
            return self

    def shutdown(self):
        with self._lock:
            self.initialized = False
            self.device = None
            self.mesh = None


_manager: Optional[DeviceManager] = None
_mgr_lock = threading.Lock()


def device_manager() -> DeviceManager:
    global _manager
    with _mgr_lock:
        if _manager is None:
            _manager = DeviceManager()
        return _manager
