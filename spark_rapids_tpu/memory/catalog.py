"""3-tier spill store: DEVICE -> HOST -> DISK.

Port of the *contract* of the reference's RapidsBufferCatalog.scala:62-795 +
RapidsDeviceMemoryStore / RapidsHostMemoryStore / RapidsDiskStore — not the
code: tiers here hold jax device pytrees, numpy host pytrees, and .npz spill
files. The catalog is the single registry; SpillableBatch handles point into
it. Spill policy: spillable (not in-use) entries, lowest priority first,
moved one tier down until the requested bytes are freed
(SpillPriorities.scala semantics).

Background writeback (ISSUE 3, conf spark.rapids.tpu.spill.asyncWrite,
reference analog: the async spill path of RapidsBufferCatalog): a tier
hop marks the entry's TARGET tier under the catalog lock and hands the
actual byte movement (device->host copy / host->disk write + fsync) to a
single writer thread, releasing the triggering operator immediately. A
reader (`acquire`) of an entry whose writeback is still in flight waits
for it to land first, so results are identical with the writer on or
off. Catalog state transitions stay under the existing lock; the writer
takes it only for the brief finalize step, never waits on events, and
disk files are fsync'd before the hop counts as complete.
"""

from __future__ import annotations

import io
import itertools
import os
import queue
import struct
import tempfile
import threading
import time
import uuid
import zlib
from enum import IntEnum
from typing import Dict, List, Optional

import jax
import numpy as np

from ..config import (HOST_SPILL_LIMIT, SPILL_ASYNC_WRITE, SPILL_DIR,
                      active_conf)
from .. import faults
from ..obs.dispatch import host_read


class StorageTier(IntEnum):
    DEVICE = 0
    HOST = 1
    DISK = 2


# reference SpillPriorities.scala
ACTIVE_ON_DECK_PRIORITY = 100
ACTIVE_BATCHING_PRIORITY = 50
OUTPUT_FOR_SHUFFLE_PRIORITY = 0
HOST_MEMORY_BUFFER_PRIORITY = -100


def _leaf_nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "nbytes"))


class _Entry:
    __slots__ = ("handle_id", "tier", "device_tree", "host_leaves", "treedef",
                 "disk_path", "nbytes", "priority", "in_use", "closed",
                 "writeback", "pending_device", "owner", "seq", "origin")

    def __init__(self, handle_id, tree, priority, owner=None, seq=0,
                 origin=None):
        self.handle_id = handle_id
        self.tier = StorageTier.DEVICE
        self.device_tree = tree
        self.host_leaves = None
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.nbytes = _leaf_nbytes(tree)
        self.disk_path = None
        self.priority = priority
        self.in_use = 0
        self.closed = False
        #: event of the in-flight async tier hop, None when settled
        self.writeback: Optional[threading.Event] = None
        #: device leaves handed to the writer (to_host hop in flight)
        self.pending_device = None
        #: workload-governor ticket of the admitting query (ISSUE 7):
        #: quota accounting mirrors this entry's budget reserve/release
        #: calls against it, and an over-quota reserve spills only its
        #: owner's entries
        self.owner = owner
        #: deterministic per-catalog registration ordinal — the
        #: fault-injection work-item key (ISSUE 7 satellite): handle_id
        #: is a uuid that differs across runs, this does not
        self.seq = seq
        #: which engine seam registered the buffer (ISSUE 16: the ICI
        #: exchange tags its staged shards "ici_exchange" so the spill
        #: plane can attribute device pressure to the shuffle lane);
        #: None for plain operator state
        self.origin = origin

    @property
    def fault_key(self) -> str:
        return f"spill:{self.seq}"


#: spill file container (ISSUE 4 integrity): magic | u32 crc32 |
#: u64 payload length | npz payload. The CRC is stamped at write and
#: verified at read; a mismatch (bit rot, torn write, injected
#: corruption) quarantines the file and recovers by recompute.
_SPILL_MAGIC = b"SRTPUSP1"
_SPILL_HEADER = struct.Struct("<8sIQ")


class SpillFileCorruption(faults.IntegrityError):
    """Spill file failed its CRC32 / structure check at read."""


def _flush_events(out_events) -> None:
    """Emit buffered (kind, fields) records — called OUTSIDE the
    catalog lock (ISSUE 12 lock-blocking-call fix: the bus takes its
    own lock and writes a file; spill-path emits are buffered under the
    lock and flushed here, the PR 6 workload-governor pattern)."""
    if not out_events:
        return
    from ..obs import events as obs_events
    for kind, fields in out_events:
        obs_events.emit(kind, **fields)
    out_events.clear()


def _write_npz(path: str, host_leaves, key: Optional[str] = None) -> None:
    """Spill file write: CRC32-stamped container, durable (fsync'd)
    before the hop counts as complete. `key` is the owning entry's
    deterministic fault key (ISSUE 7 satellite: the spill writer runs
    on its own thread, so without a work-item key the injection
    PLACEMENT — which entry's write draws the fault — depended on
    thread scheduling; keyed, placement replays exactly)."""
    buf = io.BytesIO()
    # contract: ok lock-blocking-call — reached under the catalog lock
    # only on the SYNC spill lane and the dead-writer drain (both by
    # design: the entry must not be observable mid-hop); steady-state
    # async writes run on the writer thread lock-free
    np.savez(buf, **{str(i): a for i, a in enumerate(host_leaves)})
    payload = buf.getvalue()
    # fault point: kind=io dies here (the entry stays on HOST);
    # kind=corrupt flips a byte of the STORED payload after the true CRC
    # is taken, so the damage is exactly what the read-side check catches
    crc = zlib.crc32(payload)
    payload = faults.apply("spill.disk_write", payload, key=key)
    # contract: ok lock-blocking-call — see the savez note above
    with open(path, "wb") as f:
        f.write(_SPILL_HEADER.pack(_SPILL_MAGIC, crc, len(payload)))
        f.write(payload)
        f.flush()
        # contract: ok lock-blocking-call — see the savez note above
        os.fsync(f.fileno())


def _read_npz(path: str, key: Optional[str] = None) -> List[np.ndarray]:
    """Verified spill file read: any structural or checksum failure
    raises SpillFileCorruption (the caller quarantines + recomputes)."""
    faults.check("spill.disk_read", key=key)
    # contract: ok lock-blocking-call — disk unspill runs under the
    # catalog RLock by design (atomic promotion, module docstring); the
    # async writer never calls this
    with open(path, "rb") as f:
        header = f.read(_SPILL_HEADER.size)
        if len(header) < _SPILL_HEADER.size:
            raise SpillFileCorruption(f"truncated spill header: {path}")
        magic, crc, length = _SPILL_HEADER.unpack(header)
        if magic != _SPILL_MAGIC:
            raise SpillFileCorruption(f"bad spill magic: {path}")
        payload = f.read(length)
    if len(payload) != length or zlib.crc32(payload) != crc:
        raise SpillFileCorruption(f"spill file checksum mismatch: {path}")
    with np.load(io.BytesIO(payload)) as z:
        return [z[str(i)] for i in range(len(z.files))]


class BufferCatalog:
    def __init__(self):
        self._entries: Dict[str, _Entry] = {}
        self._lock = threading.RLock()
        self.spilled_device_bytes = 0
        self.spilled_host_bytes = 0
        self._spill_dir: Optional[str] = None
        self._write_q: Optional["queue.Queue"] = None
        self._writer: Optional[threading.Thread] = None
        #: deterministic registration ordinal (fault-injection keys)
        self._add_seq = itertools.count(1)

    # -- registration ------------------------------------------------------
    def add(self, tree, priority: int = ACTIVE_BATCHING_PRIORITY,
            origin: Optional[str] = None) -> str:
        """Register a device pytree; returns a handle id. Accounts its
        footprint against the HBM budget, attributed to the admitting
        query's workload ticket (ISSUE 7 quota accounting). `origin`
        labels the registering seam for introspection
        (bytes_by_origin)."""
        from .budget import memory_budget
        from ..exec import workload
        handle = uuid.uuid4().hex
        owner = workload.current_ticket()
        with self._lock:
            seq = next(self._add_seq)
        entry = _Entry(handle, tree, priority, owner=owner, seq=seq,
                       origin=origin)
        memory_budget().reserve(entry.nbytes)
        workload.charge(owner, entry.nbytes)
        with self._lock:
            self._entries[handle] = entry
        return handle

    def acquire(self, handle: str):
        """Return the device pytree, promoting back up tiers if spilled.
        Marks the entry in-use (unspillable) until `release`. An entry
        whose async writeback is still in flight is waited for OUTSIDE
        the lock (the writer needs the lock to finish the hop)."""
        while True:
            evs: List[tuple] = []
            try:
                with self._lock:
                    entry = self._entries[handle]
                    assert not entry.closed, "acquire after close"
                    ev = entry.writeback
                    if ev is None or ev.is_set():
                        entry.writeback = None
                        if entry.tier != StorageTier.DEVICE:
                            self._unspill_locked(entry, evs)
                        entry.in_use += 1
                        return entry.device_tree
            finally:
                _flush_events(evs)
            # bounded wait + watchdog: a writer that died with this
            # hop still queued would otherwise park us here forever.
            # The lifecycle governor checks here too (ISSUE 6): a
            # cancelled/expired query blocked on an in-flight writeback
            # unwinds with spill-wait phase attribution instead of
            # waiting the hop out
            from ..exec import lifecycle
            from ..obs import phase as obs_phase
            lifecycle.check_current("spill-wait")
            t0w = time.perf_counter_ns()
            try:
                if not ev.wait(timeout=1.0):
                    self._writer_ok()
            finally:
                # phase attribution (ISSUE 17): blocked-on-writeback
                # time, accrued even when check_current raises next
                obs_phase.add("spill-wait",
                              time.perf_counter_ns() - t0w)
            lifecycle.check_current("spill-wait")

    def release(self, handle: str):
        with self._lock:
            entry = self._entries.get(handle)
            if entry is not None:
                entry.in_use = max(0, entry.in_use - 1)

    def remove(self, handle: str):
        from .budget import memory_budget
        with self._lock:
            entry = self._entries.pop(handle, None)
            if entry is None or entry.closed:
                return
            entry.closed = True  # an in-flight writeback sees this and
            # discards its result (incl. unlinking a just-written file)
        if entry.tier == StorageTier.DEVICE:
            memory_budget().release(entry.nbytes)
            from ..exec import workload
            workload.discharge(entry.owner, entry.nbytes)
        if entry.disk_path and os.path.exists(entry.disk_path):
            os.unlink(entry.disk_path)

    def tier_of(self, handle: str) -> StorageTier:
        with self._lock:
            return self._entries[handle].tier

    def size_of(self, handle: str) -> int:
        with self._lock:
            return self._entries[handle].nbytes

    # -- spilling ----------------------------------------------------------
    def synchronous_spill(self, target_bytes: Optional[int],
                          events_out: Optional[List[threading.Event]] = None,
                          owner=None) -> int:
        """Move spillable DEVICE entries to HOST (lowest priority first)
        until target_bytes are freed (None = spill everything spillable).
        Overflows HOST to DISK past the host limit. Returns bytes freed from
        device (reference DeviceMemoryEventHandler.scala:58-90 loop). With
        spill.asyncWrite the copies run on the writer thread and this
        returns as soon as the hand-offs are queued; `events_out` then
        collects each queued device->host hop's completion event, so a
        caller under budget pressure can wait for exactly the copies ITS
        spill started instead of draining the whole writer queue.

        `owner` (ISSUE 7): restrict victims to entries owned by that
        workload ticket — the over-quota reserve path spills the
        offending query's own working set, never a neighbor's."""
        from .budget import memory_budget
        from ..exec import workload
        async_write = bool(active_conf().get(SPILL_ASYNC_WRITE))
        t0s = time.perf_counter_ns()
        freed = 0
        while target_bytes is None or freed < target_bytes:
            evs: List[tuple] = []
            try:
                with self._lock:
                    candidates = [e for e in self._entries.values()
                                  if e.tier == StorageTier.DEVICE and
                                  e.in_use == 0 and not e.closed and
                                  (owner is None or e.owner is owner)]
                    if not candidates:
                        break
                    victim = min(candidates, key=lambda e: e.priority)
                    self._spill_to_host_locked(victim, async_write, evs)
                    if async_write and events_out is not None:
                        events_out.append(victim.writeback)
                    freed += victim.nbytes
            finally:
                # spill/spill_error events land OUTSIDE the catalog
                # lock (ISSUE 12), incl. on the raise path
                _flush_events(evs)
            if not async_write:
                # async: the device buffer is still physically alive in
                # entry.pending_device until the writer's device_get
                # lands — the writer releases the budget then, so the
                # accounting never under-reports live HBM
                memory_budget().release(victim.nbytes)
                workload.discharge(victim.owner, victim.nbytes)
        self._enforce_host_limit(async_write, owner=owner)
        # phase attribution (ISSUE 17): the pass ran on the thread
        # whose reservation hit pressure — its wall is that query's
        # spill-wait share (the async lane's queued hops are waited
        # for, and accrued, at the acquire/budget seams instead)
        from ..obs import phase as obs_phase
        obs_phase.add("spill-wait", time.perf_counter_ns() - t0s)
        if freed:
            # per-query spill attribution (ISSUE 11): the reserving
            # thread's governed query experienced this pressure —
            # active_queries() reports it per in-flight query
            from ..exec import lifecycle
            lifecycle.note_spill(freed)
        return freed

    def _spill_to_host_locked(self, entry: _Entry, async_write: bool,
                              out_events: List[tuple]):
        leaves = jax.tree_util.tree_leaves(entry.device_tree)
        entry.device_tree = None
        entry.tier = StorageTier.HOST
        if async_write:
            # hand the device buffers to the writer and return: the
            # triggering operator is released as soon as the copy starts
            entry.pending_device = leaves
            entry.writeback = threading.Event()
            self._enqueue_writeback("to_host", entry, None,
                                    entry.writeback, out_events)
        else:
            try:
                faults.check("spill.d2h_copy", key=entry.fault_key)
                # contract: ok lock-blocking-call — the SYNC lane
                # (asyncWrite=false) deliberately copies under the
                # catalog lock: the entry must not be observable
                # mid-hop, and the async lane exists precisely for
                # callers that cannot afford this hold
                host = host_read("spill.d2h", list(leaves))
                entry.host_leaves = [np.asarray(x) for x in host]
            except Exception as e:  # noqa: BLE001 — transient device
                # error mid-copy: the data never left the device — put
                # the entry back intact and surface a task-level retry
                # (the classified recovery for a failed movement)
                entry.device_tree = jax.tree_util.tree_unflatten(
                    entry.treedef, leaves)
                entry.tier = StorageTier.DEVICE
                out_events.append(("spill_error", dict(
                    stage="d2h_copy", sync=True, error=str(e)[:200])))
                from ..faults import TpuTaskRetryError
                raise TpuTaskRetryError(
                    f"device->host spill copy failed: {e}") from e
        self.spilled_device_bytes += entry.nbytes
        out_events.append(("spill", dict(
            tier="device->host", bytes=entry.nbytes,
            priority=entry.priority, background=async_write)))

    def _enforce_host_limit(self, async_write: bool = False, owner=None):
        """`owner` (ISSUE 7): an owner-scoped quota spill must not
        demote NEIGHBORS' host entries to disk either — the host limit
        is soft, and the next unscoped pass re-enforces it globally."""
        limit = active_conf().get(HOST_SPILL_LIMIT)
        evs: List[tuple] = []
        try:
            with self._lock:
                host_entries = [e for e in self._entries.values()
                                if e.tier == StorageTier.HOST
                                and not e.closed
                                and (owner is None or e.owner is owner)]
                host_total = sum(e.nbytes for e in host_entries)
                for e in sorted(host_entries, key=lambda x: x.priority):
                    if host_total <= limit:
                        break
                    # a sync disk-write failure leaves the entry on
                    # HOST (returns False): don't count those bytes as
                    # moved, or the pass stops early without trying
                    # other candidates
                    if self._spill_to_disk_locked(e, async_write, evs):
                        host_total -= e.nbytes
        finally:
            _flush_events(evs)  # spill events outside the lock (ISSUE 12)

    def _spill_to_disk_locked(self, entry: _Entry, async_write: bool,
                              out_events: List[tuple]) -> bool:
        """Returns True when the hop landed (or was queued to the
        writer); False when a sync write failed and the entry stayed on
        the HOST tier."""
        path = os.path.join(self._spill_dir_path(),
                            f"spill-{entry.handle_id}.npz")
        entry.tier = StorageTier.DISK
        if entry.writeback is not None and not entry.writeback.is_set():
            # a device->host copy for this entry is still in flight
            # (asyncWrite toggled off mid-query): the disk hop must go
            # through the writer queue too — FIFO lands it after the
            # copy; waiting here would deadlock on the catalog lock
            async_write = True
        if async_write:
            # FIFO on the single writer thread: a pending to_host hop
            # for this entry lands before this job runs
            entry.writeback = threading.Event()
            self._enqueue_writeback("to_disk", entry, path,
                                    entry.writeback, out_events)
        else:
            try:
                _write_npz(path, entry.host_leaves, key=entry.fault_key)
            except Exception as e:  # noqa: BLE001 — disk full/
                # unwritable: the host copy is intact, so staying on the
                # HOST tier (over its soft limit) beats failing the
                # query; the next enforcement pass will try again
                entry.tier = StorageTier.HOST
                try:
                    os.unlink(path)
                except OSError:
                    pass
                out_events.append(("spill_error", dict(
                    stage="disk_write", sync=True, error=str(e)[:200])))
                return False
            entry.host_leaves = None
            entry.disk_path = path
        self.spilled_host_bytes += entry.nbytes
        out_events.append(("spill", dict(
            tier="host->disk", bytes=entry.nbytes,
            priority=entry.priority, background=async_write)))
        return True

    def _unspill_locked(self, entry: _Entry, out_events: List[tuple]):
        from .budget import memory_budget
        if entry.tier == StorageTier.DISK:
            try:
                entry.host_leaves = _read_npz(entry.disk_path,
                                              key=entry.fault_key)
            except SpillFileCorruption as e:
                # integrity failure: quarantine the evidence (never feed
                # corrupt bytes downstream) and recover by recompute —
                # the task-attempt layer re-executes from the sources
                qpath = entry.disk_path + ".quarantined"
                try:
                    os.replace(entry.disk_path, qpath)
                    entry.disk_path = qpath  # remove() still cleans up
                except OSError:
                    pass
                out_events.append(("integrity_fail", dict(
                    what="spill_file", path=entry.disk_path,
                    bytes=entry.nbytes, error=str(e)[:200])))
                # provenance (ISSUE 6): a spill entry is intermediate
                # state with no captured lineage — the task-retry layer
                # sees this as AMBIGUOUS provenance and takes the
                # whole-plan lane (docs/robustness.md)
                e.provenance = {"kind": "spill_file",
                                "handle": entry.handle_id}
                raise
            except OSError as e:
                out_events.append(("spill_error", dict(
                    stage="disk_read", sync=True, error=str(e)[:200])))
                from ..faults import TpuTaskRetryError
                raise TpuTaskRetryError(
                    f"spill file unreadable: {e}") from e
            os.unlink(entry.disk_path)
            entry.disk_path = None
            entry.tier = StorageTier.HOST
        if entry.tier == StorageTier.HOST:
            # caller holds self._lock: must NOT drain the writer (it
            # needs this lock to finalize) — see MemoryBudget.reserve
            memory_budget().reserve(entry.nbytes,
                                    wait_for_writeback=False)
            from ..exec import workload
            workload.charge(entry.owner, entry.nbytes)
            # unspill ingest seam (ISSUE 10): the whole spilled tree
            # crosses host->device as ONE packed upload (per-leaf
            # jnp.asarray when packedUpload is off), keyed by the
            # entry's deterministic registration ordinal for seeded
            # chaos. The upload can now FAIL (injected device fault /
            # real device error) between the charge above and the tier
            # flip below — unwind both, or the entry stays HOST with
            # the reservation and quota charge leaked forever (remove()
            # only releases DEVICE-tier entries, and a retried acquire
            # would charge again)
            from ..columnar.upload import upload_leaves
            try:
                # contract: ok lock-blocking-call — unspill promotes
                # under the catalog RLock by design (atomic: the entry
                # must not be observable mid-promotion; module
                # docstring); reserve above uses the documented
                # lock-safe wait_for_writeback=False form
                leaves = upload_leaves(entry.host_leaves,
                                       fault_key=f"unspill:{entry.seq}",
                                       seam="unspill")
            except BaseException:
                memory_budget().release(entry.nbytes)
                workload.discharge(entry.owner, entry.nbytes)
                raise
            entry.device_tree = jax.tree_util.tree_unflatten(
                entry.treedef, leaves)
            entry.host_leaves = None
            entry.tier = StorageTier.DEVICE

    def _spill_dir_path(self) -> str:
        if self._spill_dir is None:
            conf_dir = active_conf().get(SPILL_DIR)
            self._spill_dir = conf_dir or tempfile.mkdtemp(prefix="srtpu-spill-")
            os.makedirs(self._spill_dir, exist_ok=True)
        return self._spill_dir

    # -- background writer -------------------------------------------------
    def _enqueue_writeback(self, kind: str, entry: _Entry,
                           path: Optional[str], ev: threading.Event,
                           out_events: List[tuple]) -> None:
        """Queue one tier hop's byte movement (caller holds the lock;
        `ev` is THAT hop's completion event — entry.writeback may point
        at a later hop by the time the job runs). A dead writer thread
        (killed by something harsher than the per-job except) is
        detected here: its stranded queue is drained synchronously and a
        fresh writer spawned, so one writer death never wedges spilling
        for the rest of the process."""
        if self._writer is not None and not self._writer.is_alive():
            self._recover_dead_writer_locked(out_events)
        if self._write_q is None:
            self._write_q = queue.Queue()
            self._writer = threading.Thread(
                target=self._writer_loop, args=(self._write_q,),
                name="spill-writer", daemon=True)
            self._writer.start()
        from ..obs import events as obs_events
        # the enqueuing query's id rides the job (ISSUE 12 thread-adopt
        # fix): the singleton writer serves EVERY query — per-job
        # adoption keeps async spill_error events attributed instead of
        # landing with query: null
        # contract: ok lock-blocking-call — unbounded queue: put() never
        # blocks, it is a list append under the queue's own mutex
        self._write_q.put((kind, entry, path, ev,
                           obs_events.current_query_id()))

    def _recover_dead_writer_locked(self, out_events: List[tuple]
                                    ) -> None:
        """Caller holds the catalog lock. Drain the dead writer's queue
        synchronously (running each stranded hop's byte movement on THIS
        thread — the 'queue drained synchronously' watchdog of ISSUE 4)
        and detach it so the next enqueue starts a fresh writer. The
        spill_writer_dead event is buffered into `out_events` (flushed
        by the caller outside the lock, ISSUE 12)."""
        from ..obs import events as obs_events
        q, self._write_q, self._writer = self._write_q, None, None
        out_events.append(("spill_writer_dead", dict(
            pending=q.qsize() if q is not None else 0)))
        if q is None:
            return
        while True:
            try:
                job = q.get_nowait()
            except queue.Empty:
                return
            if job is None:
                q.task_done()
                continue
            kind, entry, path, ev, qid = job
            try:
                # NOTE: we already hold self._lock (RLock) — fine, the
                # writeback takes it re-entrantly for its finalize
                # steps. Each stranded job still runs under ITS query's
                # event attribution, not the detecting thread's.
                obs_events.with_query_id(qid, self._run_writeback,
                                         kind, entry, path)
            except Exception:  # noqa: BLE001 — same contract as the
                pass           # writer loop: the event must still set
            finally:
                ev.set()
                q.task_done()

    def _writer_ok(self) -> None:
        """Watchdog probe used by waiters and the drain/shutdown entry
        points: if the writer died with jobs still queued, drain them
        synchronously. No return value — callers re-check their own
        wait condition afterwards."""
        evs: List[tuple] = []
        try:
            with self._lock:
                if self._writer is not None and \
                        not self._writer.is_alive():
                    self._recover_dead_writer_locked(evs)
        finally:
            _flush_events(evs)

    def _writer_loop(self, q: "queue.Queue") -> None:
        # the queue travels as an argument, not through self._write_q:
        # shutdown_writer detaches the attribute while this thread may
        # still be finishing the drained jobs
        from ..obs import events as obs_events
        while True:
            job = q.get()
            if job is None:
                q.task_done()
                return
            kind, entry, path, ev, qid = job
            try:
                # per-job query attribution (ISSUE 12): the enqueuing
                # thread's id rides the job so the writer's spill_error
                # events don't land with query: null
                obs_events.with_query_id(qid, self._run_writeback,
                                         kind, entry, path)
            except Exception:  # noqa: BLE001 — a failed writeback must
                # not kill the writer; the event is still set so waiters
                # don't hang (they will fail loudly on the missing data)
                pass
            finally:
                ev.set()
                q.task_done()

    def _run_writeback(self, kind: str, entry: _Entry,
                       path: Optional[str]) -> None:
        """One hop's data movement. The expensive part (d2h copy / file
        write + fsync) runs WITHOUT the catalog lock; only the state
        finalize takes it."""
        if kind == "to_host":
            from .budget import memory_budget
            from ..exec import workload
            with self._lock:
                pending = entry.pending_device
                if entry.closed:
                    # removed before the copy started: don't waste a
                    # full d2h transfer on a dead buffer — drop it,
                    # free the budget it still held, and un-count the
                    # hop (no bytes ever moved; keeps the counters
                    # consistent with the failure branches below)
                    entry.pending_device = None
                    if pending is not None:
                        memory_budget().release(entry.nbytes)
                        workload.discharge(entry.owner, entry.nbytes)
                        self.spilled_device_bytes -= entry.nbytes
                    return
            if pending is None:
                return
            try:
                faults.check("spill.d2h_copy", key=entry.fault_key)
                # contract: ok lock-blocking-call — lock-free on the
                # writer thread (steady state); under the catalog RLock
                # only on the dead-writer synchronous drain (recovery)
                host = [np.asarray(x) for x in host_read("spill.d2h",
                                                         list(pending))]
            except Exception as e:  # noqa: BLE001 — transient device
                # error: the data never left the device; put the entry
                # back on the DEVICE tier intact (budget never released)
                from ..obs import events as obs_events
                # contract: ok lock-blocking-call — lock-free on the
                # writer thread; under the RLock only on the dead-writer
                # drain (rare recovery; the bus lock is the leaf)
                obs_events.emit("spill_error", stage="d2h_copy",
                                sync=False, error=str(e)[:200])
                with self._lock:
                    entry.pending_device = None
                    if not entry.closed:
                        entry.device_tree = jax.tree_util.tree_unflatten(
                            entry.treedef, pending)
                        entry.tier = StorageTier.DEVICE
                        # the hop never happened: un-count it so a
                        # retried spill of this entry isn't double-counted
                        self.spilled_device_bytes -= entry.nbytes
                        return
                memory_budget().release(entry.nbytes)
                workload.discharge(entry.owner, entry.nbytes)
                return
            with self._lock:
                entry.pending_device = None
                if not entry.closed:
                    entry.host_leaves = host
            # the device buffers are dropped HERE (copy landed or entry
            # closed): only now is the HBM actually free
            memory_budget().release(entry.nbytes)
            workload.discharge(entry.owner, entry.nbytes)
            return
        # to_disk: by single-writer FIFO the to_host hop (if any) has
        # already landed, so host_leaves is populated
        with self._lock:
            host = entry.host_leaves
            closed = entry.closed
            if host is None or closed:
                # the disk write will never run (the preceding to_host
                # copy failed and restored the entry to DEVICE, or the
                # buffer was removed first): un-count the bytes
                # _spill_to_disk_locked charged for the hop
                self.spilled_host_bytes -= entry.nbytes
        if closed or host is None:
            return
        try:
            _write_npz(path, host, key=entry.fault_key)
        except Exception as e:  # noqa: BLE001 — disk full/unwritable:
            # the host copy is still intact, so the entry simply stays
            # on the HOST tier; drop any partial file
            from ..obs import events as obs_events
            # contract: ok lock-blocking-call — lock-free on the writer
            # thread; under the RLock only on the dead-writer drain
            # (rare recovery; the bus lock is the leaf)
            obs_events.emit("spill_error", stage="disk_write",
                            sync=False, error=str(e)[:200])
            with self._lock:
                if not entry.closed:
                    entry.tier = StorageTier.HOST
                    # un-count the hop that never landed (a retried
                    # disk spill would double-count this entry)
                    self.spilled_host_bytes -= entry.nbytes
            try:
                os.unlink(path)
            except OSError:
                pass
            return
        with self._lock:
            if entry.closed:
                unlink = True
            else:
                entry.host_leaves = None
                entry.disk_path = path
                unlink = False
        if unlink:
            try:
                os.unlink(path)
            except OSError:
                pass

    def drain_writeback(self) -> None:
        """Block until every queued writeback has landed (test/bench
        hook; queries never need it — acquire() waits per entry)."""
        self._writer_ok()  # a dead writer is drained synchronously here
        with self._lock:  # snapshot: shutdown_writer detaches under
            q = self._write_q  # the same lock
        if q is not None:
            q.join()

    def shutdown_writer(self) -> None:
        """Stop the writer thread after draining (test isolation). The
        queue is DETACHED under the catalog lock first: _enqueue runs
        under that lock, so a concurrent spill either lands its job
        before the drain below or sees _write_q None and starts a fresh
        writer — it can never enqueue onto a queue whose writer already
        exited (that hop's completion event would never be set and a
        later acquire() of the entry would wait forever)."""
        self._writer_ok()  # a dead writer's stranded jobs drain here
        with self._lock:
            q, writer = self._write_q, self._writer
            self._write_q = None
            self._writer = None
        if q is not None:
            q.join()
            q.put(None)
            writer.join()

    # -- introspection (test surface) -------------------------------------
    def device_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values()
                       if e.tier == StorageTier.DEVICE and not e.closed)

    def num_entries(self) -> int:
        with self._lock:
            return len(self._entries)

    def bytes_by_owner(self):
        """Per-owner resident-byte attribution for the telemetry plane
        (ISSUE 11): ({owner: device bytes}, {owner: host bytes},
        device total, host total), all from ONE lock pass so the
        per-owner sums equal the totals EXACTLY at this snapshot.
        Owners are the admitting workload tickets (`q<ticket_id>`);
        entries from ungoverned queries land under `unowned`. An entry
        whose async writeback is still in flight counts at its TARGET
        tier (the tier field the hop already flipped) — the documented
        one-in-flight-writeback tolerance of the attribution."""
        dev: Dict[str, int] = {}
        host: Dict[str, int] = {}
        dev_total = 0
        host_total = 0
        with self._lock:
            for e in self._entries.values():
                if e.closed:
                    continue
                owner = f"q{e.owner.ticket_id}" if e.owner is not None \
                    else "unowned"
                if e.tier == StorageTier.DEVICE:
                    dev[owner] = dev.get(owner, 0) + e.nbytes
                    dev_total += e.nbytes
                elif e.tier == StorageTier.HOST:
                    host[owner] = host.get(owner, 0) + e.nbytes
                    host_total += e.nbytes
        return dev, host, dev_total, host_total

    def bytes_by_origin(self):
        """Per-seam resident-byte attribution (ISSUE 16): {origin:
        (device bytes, host bytes)} over open entries, untagged entries
        under "untagged". One lock pass, same writeback tolerance as
        bytes_by_owner. The ICI shuffle's staged shards show up here
        under "ici_exchange" — the spill-contract test surface."""
        out: Dict[str, list] = {}
        with self._lock:
            for e in self._entries.values():
                if e.closed:
                    continue
                row = out.setdefault(e.origin or "untagged", [0, 0])
                if e.tier == StorageTier.DEVICE:
                    row[0] += e.nbytes
                elif e.tier == StorageTier.HOST:
                    row[1] += e.nbytes
        return {k: tuple(v) for k, v in out.items()}


_catalog: Optional[BufferCatalog] = None
_catalog_lock = threading.Lock()


def buffer_catalog() -> BufferCatalog:
    global _catalog
    with _catalog_lock:
        if _catalog is None:
            _catalog = BufferCatalog()
        return _catalog


def reset_buffer_catalog() -> BufferCatalog:
    global _catalog
    with _catalog_lock:
        old, _catalog = _catalog, BufferCatalog()
    if old is not None:
        try:
            old.shutdown_writer()
        except Exception:  # noqa: BLE001 — teardown only
            pass
    return _catalog
