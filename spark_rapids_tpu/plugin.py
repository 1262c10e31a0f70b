"""Plugin lifecycle shell — the reference's Plugin.scala
(RapidsDriverPlugin :412 / RapidsExecutorPlugin :484): startup validation,
device + memory runtime initialization, heartbeat wiring, and the
fatal-error → exit policy (:640-662: a fatal CUDA error logs diagnostics
and kills the executor so the cluster manager reschedules).

Standalone shape: there is no Spark JVM to plug into, so the lifecycle is
an explicit object the embedding application (or TpuSession) drives:
`TpuExecutorPlugin(conf).init()` … `.shutdown()`. The checks mirror the
reference's init order (SURVEY §3.1): environment validation → device
acquisition → memory runtime → shuffle/heartbeats → admission semaphore.
"""

from __future__ import annotations

import logging
import os
from typing import Callable, List, Optional

log = logging.getLogger("spark_rapids_tpu.plugin")


class FatalDeviceError(Exception):
    """Unrecoverable device/runtime failure (the reference's
    CudaFatalException classification)."""


class TpuDriverPlugin:
    """Driver side (reference RapidsDriverPlugin.init :412): conf fixups
    + heartbeat manager for executor peer discovery."""

    def __init__(self, conf=None):
        from .config import RapidsConf, active_conf
        self.conf: RapidsConf = conf or active_conf()
        self.heartbeat_manager = None

    def init(self) -> "TpuDriverPlugin":
        from .parallel.heartbeat import HeartbeatManager
        self.heartbeat_manager = HeartbeatManager()
        log.info("TpuDriverPlugin initialized (heartbeat manager up)")
        return self

    def shutdown(self) -> None:
        self.heartbeat_manager = None


class TpuExecutorPlugin:
    """Executor side (reference RapidsExecutorPlugin.init :484)."""

    def __init__(self, conf=None, executor_id: str = "exec-0",
                 driver: Optional[TpuDriverPlugin] = None,
                 exit_fn: Callable[[int], None] = None):
        from .config import RapidsConf, active_conf
        self.conf: RapidsConf = conf or active_conf()
        self.executor_id = executor_id
        self.driver = driver
        self.heartbeat_endpoint = None
        self.peers: List[str] = []
        #: test seam: production exits the process like Plugin.scala:655
        self._exit = exit_fn or (lambda code: os._exit(code))
        self._initialized = False

    # -- init sequence (reference order, SURVEY §3.1) ----------------------
    def init(self) -> "TpuExecutorPlugin":
        self._validate_environment()
        self._init_device_and_memory()
        self._init_heartbeats()
        self._init_semaphore()
        self._initialized = True
        log.info("TpuExecutorPlugin %s initialized", self.executor_id)
        return self

    def _validate_environment(self) -> None:
        """Platform checks (reference validateGpuArchitecture +
        checkCudfVersion + driver/executor timezone equality)."""
        import jax
        if not jax.devices():
            raise FatalDeviceError("no XLA devices visible")
        # the engine's kernels assume UTC session timezone (non-UTC goes
        # through the timezone DB); reject a mismatched TZ env like the
        # reference rejects driver/executor timezone mismatches
        tz = os.environ.get("TZ")
        if tz not in (None, "", "UTC", "Etc/UTC"):
            log.warning(
                "process TZ=%s; the engine computes in UTC and applies "
                "zone rules via the timezone DB (reference requires "
                "matching driver/executor timezones)", tz)

    def _init_device_and_memory(self) -> None:
        from .memory.device_manager import device_manager
        try:
            device_manager().initialize()
        except Exception as e:  # noqa: BLE001 — classified below
            self.on_fatal_error(e)
            raise

    def _init_heartbeats(self) -> None:
        if self.driver is None or self.driver.heartbeat_manager is None:
            return
        from .parallel.heartbeat import HeartbeatEndpoint
        self.heartbeat_endpoint = HeartbeatEndpoint(
            self.driver.heartbeat_manager, self.executor_id,
            on_new_peer=lambda p: self.peers.append(p.executor_id))
        self.heartbeat_endpoint.start()

    def _init_semaphore(self) -> None:
        from .memory.semaphore import tpu_semaphore
        tpu_semaphore()

    # -- failure policy ----------------------------------------------------
    def on_fatal_error(self, exc: BaseException) -> None:
        """Reference Plugin.scala:640-662: log device diagnostics, then
        exit the executor so the scheduler replaces it (task retry IS the
        recovery model — SURVEY §5)."""
        log.error("FATAL device error: %s", exc, exc_info=exc)
        try:
            import jax
            for d in jax.devices():
                stats = getattr(d, "memory_stats", lambda: None)()
                log.error("device %s: %s", d, stats)
        except Exception:  # noqa: BLE001 — diagnostics are best-effort
            pass
        if self._classify_fatal(exc):
            log.error("executor %s exiting for reschedule",
                      self.executor_id)
            self._exit(1)

    @staticmethod
    def _classify_fatal(exc: BaseException) -> bool:
        """Which failures kill the executor (reference: CudaFatalException
        yes, retryable OOM no)."""
        from .memory.retry import TpuRetryOOM, TpuSplitAndRetryOOM
        if isinstance(exc, (TpuRetryOOM, TpuSplitAndRetryOOM)):
            return False
        if isinstance(exc, FatalDeviceError):
            return True
        name = type(exc).__name__
        return "XlaRuntimeError" in name or "RuntimeError" in name

    def on_task_failed(self, exc: BaseException) -> None:
        """Reference onTaskFailed: inspect for fatal classification."""
        if self._classify_fatal(exc):
            self.on_fatal_error(exc)

    def shutdown(self) -> None:
        if self.heartbeat_endpoint is not None:
            self.heartbeat_endpoint.stop()
        from .memory.device_manager import device_manager
        device_manager().shutdown()
        self._initialized = False
