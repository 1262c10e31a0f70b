"""HBM budget manager — the RMM-pool analog (reference
GpuDeviceManager.scala:275 initializeRmm + DeviceMemoryEventHandler.scala).

XLA owns the physical HBM allocator; this layer does *accounting*: operators
reserve their padded worst-case footprint before launching device programs.
When a reservation would exceed the budget, registered spillables are
synchronously spilled (largest-priority first) until it fits — the
DeviceMemoryEventHandler loop (:58-90) — else TpuRetryOOM is raised for the
retry framework to handle.
"""

from __future__ import annotations

import threading
from typing import Optional

from ..config import HBM_BUDGET_BYTES, HBM_POOL_FRACTION, active_conf
from .retry import TpuRetryOOM

_DEFAULT_HBM = 16 << 30  # CPU backend only: stands for one v5e chip's HBM


class MemoryBudget:
    def __init__(self, limit_bytes: Optional[int] = None):
        if limit_bytes is None:
            conf = active_conf()
            override = conf.get(HBM_BUDGET_BYTES)
            if override:
                limit_bytes = override
            else:
                limit_bytes = int(_detect_hbm() * conf.get(HBM_POOL_FRACTION))
        self.limit = limit_bytes
        self.used = 0
        self._lock = threading.Condition()
        self.peak = 0
        self.spill_requests = 0

    def reserve(self, nbytes: int, wait_for_writeback: bool = True):
        """Reserve accounting space; spill-then-raise on pressure.

        `wait_for_writeback=False` is REQUIRED when the caller holds the
        buffer-catalog lock (catalog._unspill_locked): draining waits on
        the spill-writer thread, which needs that lock to finalize — a
        guaranteed deadlock. Without the drain, pressure surfaces as
        TpuRetryOOM and the retry loop waits the writebacks out instead.

        Per-query quota (ISSUE 7): under the workload governor, a query
        past its soft share of the budget that hits THIS pressure path
        spills its OWN catalog entries (quota_spill event) and raises
        its own TpuRetryOOM when that is not enough — it must not push a
        neighbor's working set down a tier. The quota is consulted only
        here (pressure), never on the in-budget fast path, so a lone or
        ungoverned query pays nothing.
        """
        with self._lock:
            if self.used + nbytes <= self.limit:
                self.used += nbytes
                self.peak = max(self.peak, self.used)
                return
        # out of budget: try to make room by spilling catalog buffers
        from .catalog import buffer_catalog
        from ..exec import workload
        needed = nbytes - (self.limit - self.used)
        hops: list = []
        ticket = workload.current_ticket()
        quota = workload.quota_bytes(self.limit) \
            if ticket is not None else None
        over_quota = quota is not None \
            and ticket.device_bytes + nbytes > quota
        if over_quota:
            # the offender spills the offender: only entries owned by
            # THIS query's ticket are candidates
            freed = buffer_catalog().synchronous_spill(
                needed, events_out=hops, owner=ticket)
            workload.note_quota_spill(ticket, nbytes, quota, freed)
        else:
            freed = buffer_catalog().synchronous_spill(needed,
                                                       events_out=hops)
        with self._lock:
            self.spill_requests += 1
            if self.used + nbytes <= self.limit:
                self.used += nbytes
                self.peak = max(self.peak, self.used)
                return
        # async writeback (spill.asyncWrite) frees the budget only when
        # each device->host copy LANDS: wait the in-flight hops out
        # before declaring OOM
        if wait_for_writeback:
            # first only the copies THIS spill queued — a full-queue
            # drain would serialize the reserve behind unrelated (and
            # later-enqueued) hops from concurrently spilling threads
            for ev in hops:
                ev.wait()
            with self._lock:
                if self.used + nbytes <= self.limit:
                    self.used += nbytes
                    self.peak = max(self.peak, self.used)
                    return
            if not over_quota:
                # last resort: hops queued by OTHER threads' spills may
                # still hold the bytes this reservation needs. An
                # over-quota query skips it — waiting out NEIGHBORS'
                # writebacks to grab the bytes they freed is exactly the
                # stealing the quota exists to stop; its own retry lane
                # (spill_for_retry between attempts) settles instead.
                buffer_catalog().drain_writeback()
                with self._lock:
                    if self.used + nbytes <= self.limit:
                        self.used += nbytes
                        self.peak = max(self.peak, self.used)
                        return
        if over_quota:
            raise TpuRetryOOM(
                f"per-query memory quota exceeded under pressure: need "
                f"{nbytes}, query holds {ticket.device_bytes} of a "
                f"{quota}-byte share ({self.used} of {self.limit} total; "
                f"freed {freed} from own entries)")
        raise TpuRetryOOM(
            f"HBM budget exhausted: need {nbytes}, used {self.used} of "
            f"{self.limit} (freed {freed} by spill)")

    def release(self, nbytes: int):
        with self._lock:
            self.used = max(0, self.used - nbytes)
            self._lock.notify_all()


def _detect_hbm() -> int:
    """HBM of device 0 as the runtime reports it. The constant stands in
    on the CPU backend only (no device memory to size against); a TPU
    that does not report `bytes_limit` is an error, not 16 GiB."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _DEFAULT_HBM
    stats = dev.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"bytes_limit in memory_stats(): {stats!r}")
    return int(stats["bytes_limit"])


_budget: Optional[MemoryBudget] = None
_budget_lock = threading.Lock()


def memory_budget() -> MemoryBudget:
    global _budget
    with _budget_lock:
        if _budget is None:
            _budget = MemoryBudget()
        return _budget


def reset_memory_budget(limit_bytes: Optional[int] = None):
    """Test hook: install a fresh (possibly tiny) budget — the analog of the
    reference's 512MiB test RMM pool (RmmSparkRetrySuiteBase.scala:35)."""
    global _budget
    with _budget_lock:
        _budget = MemoryBudget(limit_bytes)
    return _budget


def spill_for_retry():
    """Between OOM retries, aggressively push device buffers down a tier
    (reference: synchronous spill in DeviceMemoryEventHandler).

    With spill.asyncWrite the hand-offs queued here (and writebacks
    already in flight — including the ones behind a
    reserve(wait_for_writeback=False) TpuRetryOOM from the
    unspill-under-catalog-lock path, which cannot drain itself) only
    free budget when the writer lands each device->host copy. No
    catalog lock is held between retry attempts, so this is the one
    safe place to wait the writer out before the next attempt —
    otherwise the retry loop spins through its attempts in microseconds
    while the bytes it needs are still queued behind the writer thread.

    Per-query quota (ISSUE 7): the isolation reserve() enforces must
    hold on THIS lane too — a quota TpuRetryOOM lands exactly here one
    frame up, and an unfiltered pass would push every neighbor's
    working set down a tier and wait their writebacks out so the
    offender can take the bytes they freed. While the current query is
    still over its share, only its own entries spill and only its own
    hops are waited; once it drops back under, it is no longer the
    offender and the global pass applies.
    """
    from .catalog import buffer_catalog
    from ..exec import workload
    cat = buffer_catalog()
    hops: list = []
    ticket = workload.current_ticket()
    if ticket is not None:
        quota = workload.quota_bytes(memory_budget().limit)
        if quota is not None and ticket.device_bytes > quota:
            cat.synchronous_spill(None, events_out=hops, owner=ticket)
            for ev in hops:
                ev.wait()
            return
    cat.synchronous_spill(None, events_out=hops)
    for ev in hops:
        ev.wait()
    cat.drain_writeback()
