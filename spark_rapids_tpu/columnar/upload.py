"""Packed host->device batch upload — the ingest mirror of the packed
D2H fetch (columnar/transfer.py).

Every ingest seam used to promote a decoded host batch buffer-by-buffer:
one `jnp.asarray` per data/validity/offsets array per column, ~3 x
n_columns host->device round trips per batch. On a remote-attached TPU
each transfer pays full link latency, exactly the failure mode the
packed D2H fetch killed for the device->host direction. The reference
never ships a table that way either: host-side concat results land as
ONE contiguous buffer and cross PCIe in one copy (JCudfSerialization /
HostConcatResult, SURVEY §2.5).

This module provides the mirror:

  1. a host-side packer that lays the batch (row count + per-column
     blocks, the SAME block layout as the D2H format in transfer.py,
     f64 staged as double-double float32 pairs on TPU) into ONE
     contiguous uint8 staging buffer drawn from a reusable,
     capacity-bucketed staging pool (the pinned-host-memory analog:
     conf-capped idle bytes, grow-on-miss, LRU-trimmed). At the scan
     seam a fixed-width Arrow column whose buffer already holds the
     engine's physical representation (`ArrowFixed`) is written from
     its Arrow buffers STRAIGHT into its wire block — no host `Column`
     in between, no full-width temporary — so a steady-state scan of
     such columns allocates nothing on the host; every other column
     family is built as a host `Column` first and copied in, into the
     same buffer (`counters()`: `direct_cols` / `built_cols`);
  2. ONE `jax.device_put` per batch — the single transfer, routed
     through the `device.dispatch` chaos fault point with the batch's
     work-item key;
  3. ONE jitted device unpack program per capacity-shape bucket (the
     static layout spec keys the trace, like `_pack_jit`; the spec of
     a direct-packed column is the tuple its host `Column` would give,
     so both share one program) that slices / bitcasts the buffer back
     into column arrays — byte-identical to the per-buffer lane for
     every column family.

Wired at the three ingest seams: `SourceScanExec` batch upload
(`ColumnarBatch.from_arrow`), the shuffle-read deserializer's device
promotion (`shuffle/serializer.deserialize_batch` +
`HostShuffleExchangeExec._read_partition`), and spill unspill
(`memory/catalog._unspill_locked` via `upload_leaves`). Gated by
`spark.rapids.tpu.transfer.packedUpload.enabled` (default on); column
trees the packer does not recognize keep the per-buffer lane.

CPU backends may make `device_put` a ZERO-COPY alias of the staging
buffer (PJRT kImmutableZeroCopy) — a PER-BUFFER, alignment-dependent
decision, so every upload checks its own transfer: an aliased buffer
is single-use (discarded; the device owns its bytes for the arrays'
lifetime), a copied one returns to the pool through a non-blocking
release-when-ready gate on the transfer (no upload path ever blocks on
the device — the unspill seam runs under the catalog lock).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .column import (ArrayColumn, Column, Decimal128Column, MapColumn,
                     StringColumn, StructColumn)
from .encoded import DictionaryColumn
from . import transfer as _transfer
from ..obs import op_span
from ..obs.dispatch import host_read

__all__ = [
    "StagingPool", "staging_pool", "reset_staging_pool", "counters",
    "to_device_batch", "packed_upload_batch", "promote_batch",
    "promote_stream", "upload_leaves", "metric_sink", "pack_host_batch",
]


# ---------------------------------------------------------------------------
# process counters (bench.py embeds per-record deltas, the chaos-delta
# pattern; the structural-transfer test and the conftest tripwire read
# them too)
# ---------------------------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTERS = {"uploads": 0, "packed": 0, "per_buffer": 0, "transfers": 0,
             "bytes": 0, "pack_ns": 0, "pool_hits": 0, "pool_misses": 0,
             # packed-lane columns written straight from Arrow buffers /
             # copied in from a host Column
             "direct_cols": 0, "built_cols": 0}


def _note(**deltas) -> None:
    with _COUNTER_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def counters() -> Dict[str, int]:
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


# ---------------------------------------------------------------------------
# staging-buffer pool
# ---------------------------------------------------------------------------

def _byte_bucket(n: int) -> int:
    """Round a staging size up to a power-of-two bucket (>= 256 bytes)
    so reuse hits across batches of similar shape and the device unpack
    traces once per bucket, not once per exact byte size."""
    if n <= 256:
        return 256
    return 1 << int(n - 1).bit_length()


class StagingPool:
    """Reusable host staging buffers for packed uploads — the
    pinned-host-memory pool analog. acquire() pops the bucket's most
    recently returned buffer (LIFO: cache-warm) or allocates on miss;
    release() returns it and trims the LEAST recently used idle buffers
    past the `packedUpload.poolBytes` cap. In-flight (acquired) bytes
    are tracked but never capped; the conftest tripwire asserts they
    return to zero at module boundaries."""

    def __init__(self):
        self._lock = threading.Lock()
        #: bucket size -> [(tick, buf)] appended in tick order; reuse
        #: pops the tail (newest), trim pops the head (oldest)
        self._free: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        #: buffers whose device consumers may still read them —
        #: returned to _free by the (non-blocking) sweep once every
        #: tracked device array reports ready
        self._pending: List[Tuple[np.ndarray, list]] = []
        self._tick = 0
        self._pooled = 0
        self._outstanding = 0
        self.hits = 0
        self.misses = 0
        self.trims = 0

    def release_when_ready(self, buf: np.ndarray, arrays) -> None:
        """Return `buf` to the pool once every device array in `arrays`
        reports ready — WITHOUT blocking the caller (review r2: the
        unspill seam runs under the catalog's most contended lock; a
        blocking device sync there stalls every admitted query).
        Sweeps happen on later acquire()/stats() calls; `settle()`
        flushes synchronously."""
        leaves = [a for a in jax.tree_util.tree_leaves(arrays)
                  if hasattr(a, "is_ready")]
        if not leaves:
            self.release(buf)
            return
        with self._lock:
            self._pending.append((buf, leaves))
        self._sweep()

    def _sweep(self, block: bool = False) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        still = []
        for buf, leaves in pending:
            if block:
                host_read("upload.staging", leaves, fetch=False)
            if all(a.is_ready() for a in leaves):
                self.release(buf)
            else:
                still.append((buf, leaves))
        if still:
            with self._lock:
                self._pending.extend(still)

    def settle(self) -> None:
        """Blocking flush of deferred releases (tests / tripwires)."""
        self._sweep(block=True)

    def acquire(self, nbytes: int) -> np.ndarray:
        self._sweep()  # reclaim any deferred buffers that landed
        bucket = _byte_bucket(nbytes)
        with self._lock:
            lst = self._free.get(bucket)
            if lst:
                _t, buf = lst.pop()
                self._pooled -= bucket
                self._outstanding += bucket
                self.hits += 1
                _note(pool_hits=1)
                return buf
            self.misses += 1
            self._outstanding += bucket
        _note(pool_misses=1)
        return np.empty(bucket, np.uint8)

    def release(self, buf: np.ndarray) -> None:
        bucket = int(buf.shape[0])
        from ..config import UPLOAD_POOL_BYTES, active_conf
        cap = max(int(active_conf().get(UPLOAD_POOL_BYTES)), 0)
        with self._lock:
            self._outstanding -= bucket
            self._tick += 1
            self._free.setdefault(bucket, []).append((self._tick, buf))
            self._pooled += bucket
            while self._pooled > cap:
                oldest = None
                for b, lst in self._free.items():
                    if lst and (oldest is None
                                or lst[0][0] < self._free[oldest][0][0]):
                        oldest = b
                if oldest is None:  # pragma: no cover — pooled>0 => found
                    break
                self._free[oldest].pop(0)
                self._pooled -= oldest
                self.trims += 1

    def discard(self, buf: np.ndarray) -> None:
        """Drop an acquired buffer without pooling it (the upload error
        path: on a zero-copy backend a half-dispatched program may still
        alias it, so it must never be handed out again)."""
        with self._lock:
            self._outstanding -= int(buf.shape[0])

    def outstanding_bytes(self) -> int:
        self._sweep()
        with self._lock:
            return self._outstanding

    def pooled_bytes(self) -> int:
        with self._lock:
            return self._pooled

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"pooled_bytes": self._pooled,
                    "outstanding_bytes": self._outstanding,
                    "hits": self.hits, "misses": self.misses,
                    "trims": self.trims}

    def presize(self, target_bytes: int, pool_cap: int) -> int:
        """Pre-populate one idle buffer per power-of-two bucket from
        256B up to the bucket of `target_bytes` (ISSUE 14 satellite —
        the PR 10 recorded TODO): steady-state scans pack batches at or
        under batchSizeBytes, so with the ladder pre-sized their
        acquires are all HITS and the miss counter stays at zero
        (asserted in tests/test_upload.py). Cumulative pre-sized bytes
        respect `pool_cap` (the poolBytes conf) — a 1GiB default
        batch-size target under the 256MiB default pool cap pre-sizes
        the ladder up to the cap, never past it. np.empty buffers are
        lazily paged, so an unused rung costs address space, not RSS.
        Idempotent per bucket: rungs that already have an idle or
        in-flight buffer are skipped. Returns bytes pre-allocated."""
        top = _byte_bucket(max(int(target_bytes), 256))
        added = 0
        bucket = 256
        while bucket <= top:
            with self._lock:
                have = bool(self._free.get(bucket))
                room = self._pooled + bucket <= pool_cap
            if not have and room:
                buf = np.empty(bucket, np.uint8)
                with self._lock:
                    self._tick += 1
                    self._free.setdefault(bucket, []).append(
                        (self._tick, buf))
                    self._pooled += bucket
                added += bucket
            bucket <<= 1
        return added


_POOL: Optional[StagingPool] = None
_POOL_LOCK = threading.Lock()
#: (target, cap) the process pool was last pre-sized for — configure()
#: re-presizes only when the sizing inputs actually changed
_PRESIZED_FOR: Optional[Tuple[int, int]] = None


def staging_pool() -> StagingPool:
    global _POOL
    if _POOL is None:
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = StagingPool()
    return _POOL


def reset_staging_pool() -> StagingPool:
    global _POOL, _PRESIZED_FOR
    with _POOL_LOCK:
        _POOL = StagingPool()
        _PRESIZED_FOR = None
    return _POOL


def configure(conf=None) -> None:
    """Session-configure hook (ISSUE 14 satellite): pre-size the
    staging pool's bucket ladder from spark.rapids.sql.batchSizeBytes
    so steady-state scan uploads hit pre-allocated buffers instead of
    growing on miss. Cheap and idempotent per (batchSizeBytes,
    poolBytes) pair; packedUpload.poolBytes=0 (pooling off) skips."""
    global _PRESIZED_FOR
    from ..config import (BATCH_SIZE_BYTES, UPLOAD_PACKED,
                          UPLOAD_POOL_BYTES, active_conf)
    conf = conf if conf is not None else active_conf()
    if not conf.get(UPLOAD_PACKED):
        return
    cap = max(int(conf.get(UPLOAD_POOL_BYTES)), 0)
    if cap <= 0:
        return
    target = int(conf.get(BATCH_SIZE_BYTES))
    key = (target, cap)
    with _POOL_LOCK:
        if _PRESIZED_FOR == key:
            return
        _PRESIZED_FOR = key
    staging_pool().presize(target, cap)


#: cpu-family backends can make device_put a zero-copy ALIAS of the
#: host buffer — a PER-BUFFER decision in PJRT (alignment-dependent),
#: so each upload must check ITS OWN transfer (found live: a
#: process-wide probe misclassified runs whose malloc alignment
#: differed from the probe's, and pooled reuse then rewrote bytes that
#: aliased live device arrays — intermittent cross-thread corruption)
_CPU_FAMILY: Optional[bool] = None


def _cpu_family_backend() -> bool:
    global _CPU_FAMILY
    if _CPU_FAMILY is None:
        _CPU_FAMILY = jax.default_backend() == "cpu"
    return _CPU_FAMILY


def _put_aliased(dev, buf: np.ndarray) -> bool:
    """True when `dev` zero-copy-aliases the staging buffer `buf`."""
    try:
        return dev.unsafe_buffer_pointer() == buf.ctypes.data
    except Exception:  # noqa: BLE001 — sharded/odd arrays: play safe
        return True


# ---------------------------------------------------------------------------
# direct-packed Arrow columns (the scan seam)
# ---------------------------------------------------------------------------

def _arrow_direct(at) -> bool:
    """True when an Arrow type's value buffer IS the engine's physical
    lane (same width, same meaning, no cast): the signed integers, the
    two floats, DATE as int32 days, TIMESTAMP as int64 microseconds.
    Everything else (strings, dictionaries, DECIMAL, bit-packed
    BOOLEAN, nested, NullType, any other unit or width) is built as a
    host Column."""
    import pyarrow as pa
    t = pa.types
    if t.is_timestamp(at):
        return at.unit == "us"
    return t.is_int8(at) or t.is_int16(at) or t.is_int32(at) \
        or t.is_int64(at) or t.is_float32(at) or t.is_float64(at) \
        or t.is_date32(at)


class ArrowFixed:
    """A fixed-width Arrow column (Array or ChunkedArray) standing in
    for its host `Column` in the list a packed upload takes: the packer
    writes it from the Arrow buffers straight into its wire block.
    `build()` gives the host Column for a lane that needs one."""

    __slots__ = ("arr", "dtype", "capacity")

    def __init__(self, arr, dtype, capacity: int):
        self.arr = arr
        self.dtype = dtype
        self.capacity = capacity

    @staticmethod
    def of(arr, capacity: int) -> Optional["ArrowFixed"]:
        """The stand-in for `arr`, or None where its type needs a host
        Column (chosen by the Arrow type alone)."""
        if not _arrow_direct(arr.type):
            return None
        from ..types import from_arrow as type_from_arrow
        return ArrowFixed(arr, type_from_arrow(arr.type), capacity)

    def build(self) -> Column:
        from .column import column_from_arrow, host_build
        with host_build():
            col = column_from_arrow(self.arr)
            return col.with_capacity(max(self.capacity, col.capacity))


def _pack_arrow_fixed(col: ArrowFixed, buf: np.ndarray, pos: int,
                      dd: bool) -> int:
    """Write one ArrowFixed as its wire block (data then validity) at
    `buf[pos:]`: the same bytes `_pack_host_column` gives for
    `col.build()`, written once. Chunks go one after another (offset
    and length honoured), data at null slots and the tail [n, cap) are
    zeroed in place."""
    np_dtype = col.dtype.jnp_dtype
    size, cap = np_dtype.itemsize, col.capacity
    end = pos + cap * size
    data, valid = buf[pos:end], buf[end:end + cap]
    # a double-double lane is (hi, lo) float32 pairs: _host_bytes' formula
    pair = data.view(np.float32).reshape(cap, 2) \
        if dd and np_dtype == np.float64 else None
    arr = col.arr
    row = 0
    for chunk in arr.chunks if hasattr(arr, "chunks") else (arr,):
        m = len(chunk)
        if not m:
            continue
        bitmap, values = chunk.buffers()
        off = chunk.offset
        block = data[row * size:(row + m) * size]
        if pair is None:
            block[:] = np.frombuffer(values, np.uint8, m * size, off * size)
        else:
            src = np.frombuffer(values, np.float64, m, off * 8)
            hi, lo = pair[row:row + m, 0], pair[row:row + m, 1]
            with np.errstate(over="ignore", invalid="ignore"):
                np.copyto(hi, src, casting="unsafe")
                np.subtract(src, hi, out=lo, casting="unsafe")
        if chunk.null_count == 0:
            valid[row:row + m] = 1
        else:
            lead = off % 8
            bits = np.unpackbits(
                np.frombuffer(bitmap, np.uint8, (lead + m + 7) // 8,
                              off // 8),
                bitorder="little")[lead:lead + m]
            valid[row:row + m] = bits
            block.reshape(m, size)[bits == 0] = 0  # as fill_null(0)
        row += m
    data[row * size:] = 0
    valid[row:] = 0
    return end + cap


# ---------------------------------------------------------------------------
# layout spec — one hashable description per column, sizing the host
# pack and keying the jitted device unpack (trace per capacity bucket)
# ---------------------------------------------------------------------------

def _col_spec(col: Column):
    if isinstance(col, ArrowFixed):
        return ("fix", col.dtype, str(col.dtype.jnp_dtype), col.capacity)
    if isinstance(col, DictionaryColumn):
        return ("dict", col.dtype, col.capacity, col.dict_capacity,
                col.dict_byte_capacity)
    if isinstance(col, StringColumn):
        return ("str", col.dtype, col.capacity, col.byte_capacity)
    if isinstance(col, Decimal128Column):
        return ("dec128", col.dtype, col.capacity,
                tuple(_col_spec(k) for k in col.children))
    if isinstance(col, StructColumn):
        return ("struct", col.dtype, col.capacity,
                tuple(_col_spec(k) for k in col.children))
    if isinstance(col, ArrayColumn):
        return ("array", col.dtype, col.capacity, _col_spec(col.child))
    if isinstance(col, MapColumn):
        return ("map", col.dtype, col.capacity, _col_spec(col.keys),
                _col_spec(col.values))
    return ("fix", col.dtype, str(np.dtype(col.data.dtype)), col.capacity)


def _spec_nbytes(spec) -> int:
    kind = spec[0]
    if kind == "dict":
        _, _dt, cap, dict_cap, dict_byte_cap = spec
        # codes + validity + dictionary (offsets, bytes)
        return cap * 4 + cap + (dict_cap + 1) * 4 + dict_byte_cap
    if kind == "str":
        _, _dt, cap, byte_cap = spec
        return (cap + 1) * 4 + byte_cap + cap
    if kind in ("struct", "dec128"):
        return spec[2] + sum(_spec_nbytes(s) for s in spec[3])
    if kind == "array":
        return (spec[2] + 1) * 4 + spec[2] + _spec_nbytes(spec[3])
    if kind == "map":
        return (spec[2] + 1) * 4 + spec[2] + _spec_nbytes(spec[3]) \
            + _spec_nbytes(spec[4])
    _, _dt, np_dtype, cap = spec
    return cap * np.dtype(np_dtype).itemsize + cap  # data + validity


def _packable_leaf(a) -> bool:
    return isinstance(a, np.ndarray) and a.ndim == 1


def _packable_column(col) -> bool:
    """True when the packer knows this column's class and every buffer
    is host-resident — anything else keeps the per-buffer lane."""
    if isinstance(col, ArrowFixed):
        return True
    if isinstance(col, DictionaryColumn):
        return _packable_leaf(col.codes) and _packable_leaf(col.validity) \
            and _packable_leaf(col.dict_offsets) \
            and _packable_leaf(col.dict_data)
    if isinstance(col, StringColumn):
        return _packable_leaf(col.data) and _packable_leaf(col.offsets) \
            and _packable_leaf(col.validity)
    if isinstance(col, StructColumn):  # incl. Decimal128Column
        return _packable_leaf(col.validity) \
            and all(_packable_column(k) for k in col.children)
    if isinstance(col, ArrayColumn):
        return _packable_leaf(col.offsets) and _packable_leaf(col.validity) \
            and _packable_column(col.child)
    if isinstance(col, MapColumn):
        return _packable_leaf(col.offsets) and _packable_leaf(col.validity) \
            and _packable_column(col.keys) and _packable_column(col.values)
    if type(col) is Column:
        return _packable_leaf(col.data) and _packable_leaf(col.validity)
    return False


# ---------------------------------------------------------------------------
# host-side pack (mirrors transfer._pack_column's block order exactly:
# pack_host_batch(cols, n) is byte-identical to
# np.asarray(transfer._pack_jit(device_batch)) — property-tested)
# ---------------------------------------------------------------------------

def _host_bytes(arr: np.ndarray, dd: bool) -> np.ndarray:
    """One numpy leaf as its wire bytes — the host mirror of
    transfer._bytes_of."""
    a = np.ascontiguousarray(arr)
    if a.dtype == np.bool_:
        return a.view(np.uint8)
    if a.dtype == np.float64 and dd:
        hi = a.astype(np.float32)
        lo = (a - hi.astype(np.float64)).astype(np.float32)
        pair = np.empty((a.shape[0], 2), np.float32)
        pair[:, 0] = hi
        pair[:, 1] = lo
        return pair.reshape(-1).view(np.uint8)
    return a.reshape(-1).view(np.uint8)


def _put_block(buf: np.ndarray, pos: int, block: np.ndarray) -> int:
    n = block.shape[0]
    buf[pos: pos + n] = block
    return pos + n


def _pack_host_column(col: Column, buf: np.ndarray, pos: int,
                      dd: bool) -> int:
    if isinstance(col, ArrowFixed):
        return _pack_arrow_fixed(col, buf, pos, dd)
    if isinstance(col, DictionaryColumn):
        pos = _put_block(buf, pos, _host_bytes(col.codes, dd))
        pos = _put_block(buf, pos, _host_bytes(col.dict_offsets, dd))
        pos = _put_block(buf, pos, _host_bytes(col.dict_data, dd))
        return _put_block(buf, pos, _host_bytes(col.validity, dd))
    if isinstance(col, StringColumn):
        pos = _put_block(buf, pos, _host_bytes(col.offsets, dd))
        pos = _put_block(buf, pos, _host_bytes(col.data, dd))
        return _put_block(buf, pos, _host_bytes(col.validity, dd))
    if isinstance(col, StructColumn):  # incl. Decimal128Column
        pos = _put_block(buf, pos, _host_bytes(col.validity, dd))
        for k in col.children:
            pos = _pack_host_column(k, buf, pos, dd)
        return pos
    if isinstance(col, ArrayColumn):
        pos = _put_block(buf, pos, _host_bytes(col.offsets, dd))
        pos = _put_block(buf, pos, _host_bytes(col.validity, dd))
        return _pack_host_column(col.child, buf, pos, dd)
    if isinstance(col, MapColumn):
        pos = _put_block(buf, pos, _host_bytes(col.offsets, dd))
        pos = _put_block(buf, pos, _host_bytes(col.validity, dd))
        pos = _pack_host_column(col.keys, buf, pos, dd)
        return _pack_host_column(col.values, buf, pos, dd)
    pos = _put_block(buf, pos, _host_bytes(col.data, dd))
    return _put_block(buf, pos, _host_bytes(col.validity, dd))


def pack_host_batch(cols: Sequence[Column], n: int,
                    pool: Optional[StagingPool] = None,
                    specs: Optional[tuple] = None
                    ) -> Tuple[np.ndarray, int]:
    """Lay (row count + columns) into one pooled staging buffer.
    Returns (buffer, used_bytes); the buffer is bucket-sized (>= used)
    and the device unpack ignores the tail. Caller must release() or
    discard() the buffer back to the pool. `specs` lets a caller that
    already built the layout specs (the unpack needs them too) skip a
    second tree walk."""
    dd = _transfer._dd_split()
    if specs is None:
        specs = tuple(_col_spec(c) for c in cols)
    total = 4 + sum(_spec_nbytes(s) for s in specs)
    pool = pool or staging_pool()
    buf = pool.acquire(total)
    try:
        buf[:4] = np.array([n], dtype="<i4").view(np.uint8)
        pos = 4
        for col in cols:
            pos = _pack_host_column(col, buf, pos, dd)
        assert pos == total, (pos, total)
    except BaseException:
        pool.discard(buf)
        raise
    return buf, total


# ---------------------------------------------------------------------------
# device-side unpack (ONE jitted program per (buffer bucket, layout))
# ---------------------------------------------------------------------------

def _dev_cast(raw, np_dtype: np.dtype, count: int, dd: bool):
    """uint8 wire block -> device array of `count` elements — the
    device mirror of the host views in transfer._unpack_column."""
    if np_dtype == np.bool_:
        return raw.astype(jnp.bool_)
    if np_dtype == np.float64 and dd:
        pair = jax.lax.bitcast_convert_type(
            raw.reshape(count * 2, 4), jnp.float32).reshape(count, 2)
        return pair[:, 0].astype(jnp.float64) \
            + pair[:, 1].astype(jnp.float64)
    size = np_dtype.itemsize
    if size == 1:
        return jax.lax.bitcast_convert_type(raw, np_dtype)
    if size == 8:
        # stage through uint32 pairs: TPU's X64 rewriting pass has no
        # direct 8->64 bitcast (the exact inverse of _bytes_of)
        u32 = jax.lax.bitcast_convert_type(
            raw.reshape(count * 2, 4), jnp.uint32)
        return jax.lax.bitcast_convert_type(
            u32.reshape(count, 2), np_dtype)
    return jax.lax.bitcast_convert_type(
        raw.reshape(count, size), np_dtype)


def _unpack_dev_column(spec, buf, pos: int, dd: bool):
    kind = spec[0]
    if kind == "dict":
        _, dt, cap, dict_cap, dict_byte_cap = spec
        codes = _dev_cast(buf[pos: pos + cap * 4], np.dtype(np.int32),
                          cap, dd)
        pos += cap * 4
        off = _dev_cast(buf[pos: pos + (dict_cap + 1) * 4],
                        np.dtype(np.int32), dict_cap + 1, dd)
        pos += (dict_cap + 1) * 4
        data = buf[pos: pos + dict_byte_cap]
        pos += dict_byte_cap
        v = buf[pos: pos + cap].astype(jnp.bool_)
        pos += cap
        return DictionaryColumn(codes, data, off, v, dt), pos
    if kind == "str":
        _, dt, cap, byte_cap = spec
        off = _dev_cast(buf[pos: pos + (cap + 1) * 4], np.dtype(np.int32),
                        cap + 1, dd)
        pos += (cap + 1) * 4
        data = buf[pos: pos + byte_cap]
        pos += byte_cap
        v = buf[pos: pos + cap].astype(jnp.bool_)
        pos += cap
        return StringColumn(data, off, v, dt), pos
    if kind in ("struct", "dec128"):
        dt, cap = spec[1], spec[2]
        v = buf[pos: pos + cap].astype(jnp.bool_)
        pos += cap
        kids = []
        for s in spec[3]:
            kid, pos = _unpack_dev_column(s, buf, pos, dd)
            kids.append(kid)
        cls = Decimal128Column if kind == "dec128" else StructColumn
        return cls(tuple(kids), v, dt), pos
    if kind == "array":
        dt, cap = spec[1], spec[2]
        off = _dev_cast(buf[pos: pos + (cap + 1) * 4], np.dtype(np.int32),
                        cap + 1, dd)
        pos += (cap + 1) * 4
        v = buf[pos: pos + cap].astype(jnp.bool_)
        pos += cap
        kid, pos = _unpack_dev_column(spec[3], buf, pos, dd)
        return ArrayColumn(kid, off, v, dt), pos
    if kind == "map":
        dt, cap = spec[1], spec[2]
        off = _dev_cast(buf[pos: pos + (cap + 1) * 4], np.dtype(np.int32),
                        cap + 1, dd)
        pos += (cap + 1) * 4
        v = buf[pos: pos + cap].astype(jnp.bool_)
        pos += cap
        keys, pos = _unpack_dev_column(spec[3], buf, pos, dd)
        vals, pos = _unpack_dev_column(spec[4], buf, pos, dd)
        return MapColumn(keys, vals, off, v, dt), pos
    _, dt, np_dtype, cap = spec
    np_dtype = np.dtype(np_dtype)
    nbytes = cap * np_dtype.itemsize
    data = _dev_cast(buf[pos: pos + nbytes], np_dtype, cap, dd)
    pos += nbytes
    v = buf[pos: pos + cap].astype(jnp.bool_)
    pos += cap
    return Column(data, v, dt), pos


def _unpack_batch_impl(buf, specs, dd: bool):
    num_rows = jax.lax.bitcast_convert_type(
        buf[:4].reshape(1, 4), jnp.int32)[0]
    pos = 4
    cols = []
    for s in specs:
        col, pos = _unpack_dev_column(s, buf, pos, dd)
        cols.append(col)
    return num_rows, tuple(cols)


from ..obs.dispatch import instrument as _instrument

_unpack_batch_jit = _instrument(_unpack_batch_impl,
                                label="upload.unpack_batch",
                                static_argnums=(1, 2))


def _unpack_leaves_impl(buf, specs, dd: bool):
    pos = 0
    out = []
    for np_dtype, shape in specs:
        np_dtype = np.dtype(np_dtype)
        count = int(np.prod(shape)) if shape else 1
        # dd staging is size-preserving: 2 x f32 == f64's 8 bytes
        nbytes = count * np_dtype.itemsize
        flat = _dev_cast(buf[pos: pos + nbytes], np_dtype, count, dd)
        pos += nbytes
        out.append(flat.reshape(shape))
    return tuple(out)


_unpack_leaves_jit = _instrument(_unpack_leaves_impl,
                                 label="upload.unpack_leaves",
                                 static_argnums=(1, 2))


# ---------------------------------------------------------------------------
# metric attribution (thread-local sink: the scan seam's uploads happen
# deep inside source.batches(), on the pipeline producer thread)
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextmanager
def metric_sink(num_metric, time_metric):
    """Attribute uploads inside the with-block to an exec's
    (numUploads, uploadPackTimeNs) metric pair."""
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = (num_metric, time_metric)
    try:
        yield
    finally:
        _TLS.sink = prev


def _record(lane: str, seam: str, nbytes: int, rows: int, n_cols: int,
            transfers: int, pack_ns: int, direct_cols: int = 0,
            built_cols: int = 0) -> None:
    _note(uploads=1, transfers=transfers, bytes=nbytes, pack_ns=pack_ns,
          direct_cols=direct_cols, built_cols=built_cols,
          **({"packed": 1} if lane == "packed" else {"per_buffer": 1}))
    sink = getattr(_TLS, "sink", None)
    if sink is not None:
        sink[0].add(1)
        sink[1].add(pack_ns)
    from ..obs import events as obs_events
    obs_events.emit("upload", lane=lane, seam=seam, bytes=nbytes,
                    rows=rows, cols=n_cols, transfers=transfers,
                    pack_ns=pack_ns, direct_cols=direct_cols)


# ---------------------------------------------------------------------------
# upload lanes
# ---------------------------------------------------------------------------

def _one_transfer(buf: np.ndarray, fault_key: Optional[str]):
    """The single host->device copy, routed through the
    `device.dispatch` chaos fault point with the batch's work-item key
    so seeded injection covers this lane (ISSUE 10 satellite)."""
    from .. import faults
    faults.check("device.dispatch", key=fault_key)
    return jax.device_put(buf)


def _finish_staging(pool: StagingPool, buf: np.ndarray, dev) -> None:
    """Hand the staging buffer back once it is safe to mutate again —
    `dev` readiness is the sufficient gate in every case (a ready
    device copy means the host bytes were consumed; an alias is never
    safe at all).

    CPU backend, aliased put (PJRT zero-copy — per-buffer, alignment
    dependent): `dev` references `buf`'s bytes for its whole lifetime,
    so the buffer can NEVER be rewritten — staging is single-use
    (discard; jaxlib keeps the ndarray alive for the aliasing device
    buffer). Pooling buys nothing for such puts anyway: no copy
    happened, there is nothing to amortize. Found live: 8 concurrent
    upload lanes with pooled reuse intermittently read each other's
    bytes through aliasing; single-use staging (and, independently,
    serialized uploads) are both clean.

    Copied put (CPU non-aliased, or any real accelerator's DMA): reuse
    is safe once the transfer consumed the host bytes — gate the
    release on `dev` readiness WITHOUT blocking (review r2: the
    unspill seam runs under the catalog's most contended lock; waiting
    out a remote-link DMA there stalls every admitted query). The
    deferred gate keeps the device u8 buffer alive until the next pool
    sweep — one batch-sized buffer, untracked by the HBM budget,
    bounded by upload cadence."""
    if _cpu_family_backend() and _put_aliased(dev, buf):
        pool.discard(buf)
    else:
        pool.release_when_ready(buf, dev)


def packed_upload_batch(cols: Sequence[Column], n: int, schema,
                        fault_key: Optional[str] = None,
                        seam: str = "other"):
    """The packed lane, unconditionally: ONE staging pack, ONE
    device_put, ONE jitted unpack. Callers outside tests/bench should
    use to_device_batch (conf-gated, with the per-buffer fallback)."""
    from .batch import ColumnarBatch
    t0 = time.perf_counter_ns()
    dd = _transfer._dd_split()
    specs = tuple(_col_spec(c) for c in cols)
    pool = staging_pool()
    with op_span("upload.pack", phase="upload"):
        buf, total = pack_host_batch(cols, n, pool, specs=specs)
    try:
        # ship only the used bytes, not the pool bucket: the bucket can
        # be ~2x the payload, and on a remote-attached link that halves
        # effective ingest bandwidth (the specs fix `total`, so the
        # unpack still traces once per layout — the view adds no keys)
        with op_span("upload.put", phase="upload"):
            dev = _one_transfer(buf[:total], fault_key)
            num_rows, out_cols = _unpack_batch_jit(dev, specs, dd)
    except BaseException:
        pool.discard(buf)
        raise
    _finish_staging(pool, buf, dev)
    del dev
    direct = sum(isinstance(c, ArrowFixed) for c in cols)
    _record("packed", seam, total, n, len(cols), 1,
            time.perf_counter_ns() - t0, direct_cols=direct,
            built_cols=len(cols) - direct)
    return ColumnarBatch(list(out_cols), num_rows, schema, host_rows=n)


def _per_buffer_batch(cols: Sequence[Column], n: int, schema,
                      seam: str, fault_key: Optional[str] = None):
    """The fallback lane: one transfer per host leaf (exactly the
    pre-ISSUE-10 behavior), counted so the structural tests can pin the
    difference."""
    from .batch import ColumnarBatch
    t0 = time.perf_counter_ns()
    from .. import faults
    faults.check("device.dispatch", key=fault_key)
    leaves, treedef = jax.tree_util.tree_flatten(list(cols))
    transfers = 0
    nbytes = 0
    dev_leaves = []
    with op_span("upload.put", phase="upload"):
        for leaf in leaves:
            if isinstance(leaf, np.ndarray):
                transfers += 1
                nbytes += leaf.nbytes
                dev_leaves.append(jnp.asarray(leaf))
            else:
                # already on device, or an unregistered-pytree column
                # that flattened as one opaque leaf — pass through
                # untouched (exactly the pre-ISSUE-10 behavior for such
                # trees)
                dev_leaves.append(leaf)
    out_cols = jax.tree_util.tree_unflatten(treedef, dev_leaves)
    batch = ColumnarBatch(out_cols, n, schema)  # +1: the row-count scalar
    _record("per_buffer", seam, nbytes, n, len(cols), transfers + 1,
            time.perf_counter_ns() - t0)
    return batch


def to_device_batch(cols: Sequence[Column], n: int, schema,
                    fault_key: Optional[str] = None, seam: str = "other"):
    """Promote host-built columns (and the scan seam's ArrowFixed
    stand-ins) to a device ColumnarBatch on the lane the conf selects:
    packed (one transfer) when enabled and every column is packable,
    per-buffer otherwise."""
    from ..config import UPLOAD_PACKED, active_conf
    if active_conf().get(UPLOAD_PACKED) \
            and all(_packable_column(c) for c in cols):
        return packed_upload_batch(cols, n, schema, fault_key, seam)
    cols = [c.build() if isinstance(c, ArrowFixed) else c for c in cols]
    return _per_buffer_batch(cols, n, schema, seam, fault_key)


def promote_batch(batch, fault_key: Optional[str] = None,
                  seam: str = "other"):
    """Device-promote a host-backed ColumnarBatch (numpy leaves);
    batches already on device pass through untouched."""
    leaves = jax.tree_util.tree_leaves(list(batch.columns))
    if not any(isinstance(x, np.ndarray) for x in leaves):
        return batch
    return to_device_batch(list(batch.columns), batch.num_rows_host,
                           batch.schema, fault_key, seam)


def promote_stream(it, key_prefix: str = "", seam: str = "other",
                   num_metric=None, time_metric=None):
    """Wrap a host-batch iterator with device promotion — the
    shuffle-read seam: decode stays on the reader pool, the ONE upload
    per batch runs here (on the pipeline producer thread), attributed
    to the wired exec's metric pair and keyed per batch ordinal so
    seeded chaos placement is thread-schedule independent."""
    try:
        for i, b in enumerate(it):
            key = f"{key_prefix}:{i}" if key_prefix else None
            if num_metric is not None:
                # promote INSIDE the sink, yield OUTSIDE it: a
                # generator suspends at yield with thread-locals
                # intact, and a sink left bound across the suspension
                # would swallow whatever uploads the consuming thread
                # does between pulls (e.g. an unspill)
                with metric_sink(num_metric, time_metric):
                    out = promote_batch(b, fault_key=key, seam=seam)
                yield out
            else:
                yield promote_batch(b, fault_key=key, seam=seam)
    finally:
        # closing this wrapper must close the wrapped stream too — a
        # for-loop abandons its iterator without closing it, and the
        # engine's teardown discipline is synchronous (ISSUE 6)
        close = getattr(it, "close", None)
        if close is not None:
            close()


def upload_leaves(host_leaves: Sequence[np.ndarray],
                  fault_key: Optional[str] = None,
                  seam: str = "unspill") -> List:
    """Promote a flat list of numpy leaves (a spilled pytree) with ONE
    transfer — the unspill seam. Falls back to per-leaf jnp.asarray
    when the conf gates packing off or a leaf is not a plain numpy
    array."""
    from ..config import UPLOAD_PACKED, active_conf
    leaves = list(host_leaves)
    packable = active_conf().get(UPLOAD_PACKED) and leaves \
        and all(isinstance(a, np.ndarray) for a in leaves)
    t0 = time.perf_counter_ns()
    if not packable:
        from .. import faults
        faults.check("device.dispatch", key=fault_key)
        out = [jnp.asarray(a) for a in leaves]
        _record("per_buffer", seam,
                sum(a.nbytes for a in leaves
                    if isinstance(a, np.ndarray)),
                0, len(leaves), len(leaves), time.perf_counter_ns() - t0)
        return out
    dd = _transfer._dd_split()
    specs = tuple((str(a.dtype), tuple(a.shape)) for a in leaves)
    # dd staging is size-preserving (a (hi, lo) float32 pair is exactly
    # f64's 8 bytes), so plain nbytes sizes every leaf
    total = sum(a.nbytes for a in leaves)
    pool = staging_pool()
    buf = pool.acquire(max(total, 1))
    pos = 0
    for a in leaves:
        block = _host_bytes(a.reshape(-1), dd)
        buf[pos: pos + block.shape[0]] = block
        pos += block.shape[0]
    assert pos == total, (pos, total)
    try:
        dev = _one_transfer(buf[:total], fault_key)  # used bytes only
        out = _unpack_leaves_jit(dev, specs, dd)
    except BaseException:
        pool.discard(buf)
        raise
    _finish_staging(pool, buf, dev)
    del dev
    _record("packed", seam, total, 0, len(leaves), 1,
            time.perf_counter_ns() - t0)
    return list(out)
