"""Columnar batch wire format for the host shuffle (and the disk spill /
dump tooling): the reference's GpuColumnarBatchSerializer.scala:127 +
JCudfSerialization host-buffer framing, with nvcomp LZ4 replaced by the
native block codec (native/src/blockcodec.cpp).

Frame layout (little-endian):

    magic "TPUSHUF1" | u8 version | u8 codec | u16 flags
    u64 num_rows | u64 schema_hash | u64 raw_len | u64 comp_len
    u64 checksum (xxh64 of the stored payload)
    u32 nbuf | nbuf * u64 buffer byte lengths
    payload (concatenated buffers, possibly compressed)

The buffer *structure* is fully determined by the schema (the reader
always knows it from the plan), so the header carries only byte lengths
plus a schema fingerprint to catch mismatches. Buffers per column, in
order, trimmed to the logical row count (padding never hits the wire):

    fixed-width: validity bitmask (packbits), data[:num_rows]
    string:      validity bitmask, offsets[:num_rows+1] rebased to 0,
                 bytes[:total]
    array:       validity bitmask, offsets[:num_rows+1] rebased to 0,
                 then the child's buffers for offsets[num_rows] elements
    struct:      validity bitmask, then each child's buffers
"""

from __future__ import annotations

import struct
import threading
from typing import List, Tuple

import numpy as np

from ..columnar.batch import ColumnarBatch
from ..columnar.column import (ArrayColumn, Column, MapColumn,
                               StringColumn, StructColumn,
                               bucket_capacity)
from ..native import lz4_available, lz4_compress, lz4_decompress, xxh64
from ..types import Schema

MAGIC = b"TPUSHUF1"
VERSION = 1
CODEC_COPY = 0  # reference CopyCompressionCodec
CODEC_LZ4 = 1   # reference NvcompLZ4CompressionCodec (host analog)


class CorruptFrameError(ValueError):
    """The frame's structure or checksum failed verification: the block
    is damaged (torn write, bit rot, injected corruption). The reader
    quarantines it and recovers by recompute (ISSUE 4 integrity)."""

_HEADER = struct.Struct("<8sBBHQQQQQI")


def schema_fingerprint(schema: Schema) -> int:
    return xxh64(repr([(f.name, f.data_type.simple_name())
                       for f in schema.fields]).encode())


# ---------------------------------------------------------------------------
# host-side column encode (device → trimmed numpy buffers)
# ---------------------------------------------------------------------------

def _np(x) -> np.ndarray:
    from ..obs.dispatch import host_read
    return np.asarray(host_read("shuffle.encode", x))


def _rebase_offsets(off: np.ndarray, n: int, start: int = 0) -> np.ndarray:
    out = off[start: start + n + 1].astype(np.int32, copy=True)
    return out - out[0]


def _encode_column(col: Column, n: int, out: List[np.ndarray],
                   start: int = 0) -> None:
    """Encode rows [start, start+n) of `col` into trimmed buffers. The
    `start` base makes non-compacted children (array-of-X whose referenced
    span begins past element 0) encode correctly instead of asserting."""
    out.append(np.packbits(
        _np(col.validity)[start: start + n].astype(np.bool_),
        bitorder="little"))
    if isinstance(col, StringColumn):
        off = _np(col.offsets)
        out.append(_rebase_offsets(off, n, start))
        lo = int(off[start])
        hi = int(off[start + n]) if n else lo
        out.append(_np(col.data)[lo:hi].astype(np.uint8, copy=False))
    elif isinstance(col, ArrayColumn):
        off = _np(col.offsets)
        out.append(_rebase_offsets(off, n, start))
        # the child is encoded for exactly the referenced element span
        lo = int(off[start])
        hi = int(off[start + n]) if n else lo
        _encode_column(col.child, hi - lo, out, start=lo)
    elif isinstance(col, StructColumn):
        for ch in col.children:
            _encode_column(ch, n, out, start=start)
    elif isinstance(col, MapColumn):
        off = _np(col.offsets)
        out.append(_rebase_offsets(off, n, start))
        lo = int(off[start])
        hi = int(off[start + n]) if n else lo
        _encode_column(col.keys, hi - lo, out, start=lo)
        _encode_column(col.values, hi - lo, out, start=lo)
    else:
        out.append(np.ascontiguousarray(_np(col.data)[start: start + n]))


def _decode_column(dtype, n: int, bufs: List[bytes], pos: int,
                   capacity: int) -> Tuple[Column, int]:
    """Decode one column's buffers into a column whose leaves follow
    the active build mode (`columnar.column._dev`): numpy under
    `host_build()` — the ISSUE 10 decode path, so the whole batch can
    promote to device as ONE packed upload — device-per-buffer
    otherwise."""
    from ..columnar.column import _dev
    from ..types import ArrayType, StringType, StructType

    vbits = np.frombuffer(bufs[pos], dtype=np.uint8)
    pos += 1
    validity = np.unpackbits(vbits, count=n, bitorder="little").astype(
        np.bool_) if n else np.zeros(0, np.bool_)
    vpad = np.zeros(capacity, np.bool_)
    vpad[:n] = validity

    if isinstance(dtype, StructType):
        kids = []
        for f in dtype.fields:
            k, pos = _decode_column(f.data_type, n, bufs, pos, capacity)
            kids.append(k)
        return StructColumn(tuple(kids), _dev(vpad), dtype), pos

    from ..types import DecimalType, LongType
    if isinstance(dtype, DecimalType) and dtype.precision > 18:
        from ..columnar.column import Decimal128Column
        hi, pos = _decode_column(LongType(), n, bufs, pos, capacity)
        lo, pos = _decode_column(LongType(), n, bufs, pos, capacity)
        return Decimal128Column((hi, lo), _dev(vpad), dtype), pos

    if isinstance(dtype, ArrayType):
        off = np.frombuffer(bufs[pos], dtype=np.int32)
        pos += 1
        opad = np.zeros(capacity + 1, np.int32)
        opad[: n + 1] = off
        opad[n + 1:] = off[n] if n else 0
        child_n = int(off[n]) if n else 0
        child_cap = bucket_capacity(max(child_n, 1))
        child, pos = _decode_column(dtype.element_type, child_n, bufs, pos,
                                    child_cap)
        return ArrayColumn(child, _dev(opad), _dev(vpad), dtype), pos

    from ..types import MapType
    if isinstance(dtype, MapType):
        from ..columnar.column import MapColumn
        off = np.frombuffer(bufs[pos], dtype=np.int32)
        pos += 1
        opad = np.zeros(capacity + 1, np.int32)
        opad[: n + 1] = off
        opad[n + 1:] = off[n] if n else 0
        entry_n = int(off[n]) if n else 0
        ecap = bucket_capacity(max(entry_n, 1))
        keys, pos = _decode_column(dtype.key_type, entry_n, bufs, pos,
                                   ecap)
        vals, pos = _decode_column(dtype.value_type, entry_n, bufs, pos,
                                   ecap)
        return MapColumn(keys, vals, _dev(opad), _dev(vpad), dtype), pos

    if dtype.jnp_dtype is None or isinstance(dtype, StringType):
        off = np.frombuffer(bufs[pos], dtype=np.int32)
        pos += 1
        data = np.frombuffer(bufs[pos], dtype=np.uint8)
        pos += 1
        opad = np.zeros(capacity + 1, np.int32)
        opad[: n + 1] = off
        opad[n + 1:] = off[n] if n else 0
        byte_cap = bucket_capacity(max(len(data), 1))
        dpad = np.zeros(byte_cap, np.uint8)
        dpad[: len(data)] = data
        return StringColumn(_dev(dpad), _dev(opad), _dev(vpad),
                            dtype), pos

    data = np.frombuffer(bufs[pos], dtype=dtype.jnp_dtype)
    pos += 1
    dpad = np.zeros(capacity, dtype.jnp_dtype)
    dpad[:n] = data
    return Column(_dev(dpad), _dev(vpad), dtype), pos


# ---------------------------------------------------------------------------
# frame encode/decode
# ---------------------------------------------------------------------------

def serialize_batch(batch: ColumnarBatch, codec: int = None) -> bytes:
    """Batch → one self-checking frame. Device padding is trimmed; string
    and array payloads keep only referenced bytes/elements."""
    n = batch.num_rows_host
    bufs: List[np.ndarray] = []
    for col in batch.columns:
        _encode_column(col, n, bufs)
    return _frame_from_bufs(bufs, n, batch.schema, codec)


def _frame_from_bufs(bufs: List[np.ndarray], n: int, schema: Schema,
                     codec: int = None) -> bytes:
    """Shared frame assembly: trimmed buffers -> one self-checking
    frame (the byte layout both serialize_batch and serialize_slice
    produce — the slice path is byte-identical by construction)."""
    if codec is None:
        codec = CODEC_LZ4 if lz4_available() else CODEC_COPY
    raw_parts = [np.ascontiguousarray(b).tobytes() for b in bufs]
    raw = b"".join(raw_parts)
    if codec == CODEC_LZ4:
        payload = lz4_compress(raw)
        if len(payload) >= len(raw):  # incompressible: store raw
            codec, payload = CODEC_COPY, raw
    else:
        payload = raw
    sizes = struct.pack(f"<{len(raw_parts)}Q", *map(len, raw_parts))
    # the checksum covers the WHOLE frame — header (with the checksum
    # field zeroed), size table and payload. Header fields are live
    # decode inputs (codec selects decompression, raw_len sizes it, the
    # size table is sliced by n/nbuf): a flipped bit in any of them must
    # be a detected corruption, not garbage buffers or a misclassified
    # schema mismatch
    shash = schema_fingerprint(schema)
    hdr0 = _HEADER.pack(MAGIC, VERSION, codec, 0, n, shash,
                        len(raw), len(payload), 0, len(raw_parts))
    chk = xxh64(hdr0 + sizes + payload)
    header = _HEADER.pack(MAGIC, VERSION, codec, 0, n, shash,
                          len(raw), len(payload), chk, len(raw_parts))
    return header + sizes + payload


def serialize_slice(batch: ColumnarBatch, lo: int, hi: int,
                    codec: int = None) -> bytes:
    """Encode rows [lo, hi) of a host-resident batch as one frame —
    byte-identical to `serialize_batch(host_gather_batch(batch,
    arange(lo, hi)))` but with ZERO gathers: offsets rebase in place,
    validity lanes and payload bytes slice (ISSUE 9). The device
    shuffle partitioner lands the batch partition-ordered, so every
    partition is exactly such a row range."""
    n = hi - lo
    assert 0 <= lo <= hi, (lo, hi)
    bufs: List[np.ndarray] = []
    for col in batch.columns:
        _encode_column(col, n, bufs, start=lo)
    return _frame_from_bufs(bufs, n, batch.schema, codec)


def deserialize_batch(frame: bytes, schema: Schema,
                      device: bool = True,
                      fault_key: str = None) -> ColumnarBatch:
    """Frame -> batch. Columns decode host-resident; with `device`
    (default) the batch promotes through the packed upload engine (ONE
    transfer when packedUpload is on — the shuffle-read ingest seam,
    ISSUE 10), drawing its `device.dispatch` chaos verdicts under
    `fault_key` (callers on pool/producer threads should pass their
    work-item identity so seeded placement is schedule-independent).
    `device=False` returns the host-backed batch so the caller can
    promote at its own seam (the exchange promotes on its pipeline
    producer thread, with metric attribution and per-batch chaos
    keys)."""
    if len(frame) < _HEADER.size:
        raise CorruptFrameError("truncated shuffle frame header")
    (magic, version, codec, flags, n, shash, raw_len, comp_len, chk,
     nbuf) = _HEADER.unpack_from(frame, 0)
    if magic != MAGIC or version != VERSION:
        raise CorruptFrameError("not a TPU shuffle frame")
    off = _HEADER.size
    if len(frame) < off + 8 * nbuf:
        raise CorruptFrameError("truncated shuffle frame size table")
    sizes = struct.unpack_from(f"<{nbuf}Q", frame, off)
    sizes_bytes = frame[off: off + 8 * nbuf]
    off += 8 * nbuf
    payload = frame[off: off + comp_len]
    hdr0 = _HEADER.pack(magic, version, codec, flags, n, shash,
                        raw_len, comp_len, 0, nbuf)
    if len(payload) != comp_len or xxh64(hdr0 + sizes_bytes + payload) != chk:
        raise CorruptFrameError(
            "shuffle frame checksum mismatch (corrupt block)")
    # checksum verified: a fingerprint mismatch now is a REAL schema
    # disagreement (an engine bug), not bit rot — fail loudly, don't
    # quarantine-and-recompute our way past it
    if shash != schema_fingerprint(schema):
        raise ValueError("shuffle frame schema mismatch")
    raw = lz4_decompress(payload, raw_len) if codec == CODEC_LZ4 else payload
    bufs: List[bytes] = []
    p = 0
    for s in sizes:
        bufs.append(raw[p: p + s])
        p += s
    capacity = bucket_capacity(max(n, 1))
    from ..columnar.column import host_build
    cols: List[Column] = []
    pos = 0
    with host_build():
        for f in schema.fields:
            c, pos = _decode_column(f.data_type, n, bufs, pos, capacity)
            cols.append(c)
    if not device:
        return ColumnarBatch(cols, n, schema)
    from ..columnar.upload import to_device_batch
    return to_device_batch(cols, n, schema, fault_key=fault_key,
                           seam="shuffle")


# ---------------------------------------------------------------------------
# host row gather (writer-side partition split)
# ---------------------------------------------------------------------------

#: process-cumulative count of host-side row gathers (top-level
#: host_gather_column calls; child recursions don't double-count).
#: The device partition lane (ISSUE 9) pins this at ZERO per written
#: batch on the hash/roundrobin/single lanes — the structural test and
#: bench.py's {"shuffle": ...} block both read it.
_host_gathers = 0
_host_gathers_lock = threading.Lock()


def host_gather_calls() -> int:
    with _host_gathers_lock:
        return _host_gathers


def host_gather_column(col: Column, idx: np.ndarray,
                       _toplevel: bool = True) -> Column:
    """Row-gather a device column into a compact host-backed column (used
    by the shuffle writer to split a batch into partition blocks). The
    result's arrays are numpy; serialize_batch consumes them directly."""
    from ..types import ArrayType  # noqa: F401

    if _toplevel:
        global _host_gathers
        with _host_gathers_lock:
            _host_gathers += 1
    validity = _np(col.validity)[idx] if len(idx) else np.zeros(0, np.bool_)
    cap = bucket_capacity(max(len(idx), 1))
    vpad = np.zeros(cap, np.bool_)
    vpad[: len(idx)] = validity

    if isinstance(col, StringColumn):
        off = _np(col.offsets)
        data = _np(col.data)
        starts = off[idx]
        lens = (off[idx + 1] - starts).astype(np.int64)
        total = int(lens.sum())
        new_off = np.zeros(cap + 1, np.int32)
        np.cumsum(lens, out=new_off[1: len(idx) + 1])
        new_off[len(idx) + 1:] = new_off[len(idx)]
        out = np.zeros(bucket_capacity(max(total, 1)), np.uint8)
        if total:
            cum = np.concatenate([[0], np.cumsum(lens)[:-1]])
            byte_idx = (np.repeat(starts, lens)
                        + np.arange(total) - np.repeat(cum, lens))
            out[:total] = data[byte_idx]
        return StringColumn(out, new_off, vpad, col.dtype)

    if isinstance(col, ArrayColumn):
        off = _np(col.offsets)
        starts = off[idx]
        lens = (off[idx + 1] - starts).astype(np.int64)
        total = int(lens.sum())
        new_off = np.zeros(cap + 1, np.int32)
        np.cumsum(lens, out=new_off[1: len(idx) + 1])
        new_off[len(idx) + 1:] = new_off[len(idx)]
        if total:
            cum = np.concatenate([[0], np.cumsum(lens)[:-1]])
            elem_idx = (np.repeat(starts, lens)
                        + np.arange(total) - np.repeat(cum, lens))
        else:
            elem_idx = np.zeros(0, np.int64)
        child = host_gather_column(col.child, elem_idx, _toplevel=False)
        return ArrayColumn(child, new_off, vpad,
                           col.dtype)

    if isinstance(col, MapColumn):
        off = _np(col.offsets)
        starts = off[idx]
        lens = (off[idx + 1] - starts).astype(np.int64)
        total = int(lens.sum())
        new_off = np.zeros(cap + 1, np.int32)
        np.cumsum(lens, out=new_off[1: len(idx) + 1])
        new_off[len(idx) + 1:] = new_off[len(idx)]
        if total:
            cum = np.concatenate([[0], np.cumsum(lens)[:-1]])
            entry_idx = (np.repeat(starts, lens)
                         + np.arange(total) - np.repeat(cum, lens))
        else:
            entry_idx = np.zeros(0, np.int64)
        keys = host_gather_column(col.keys, entry_idx, _toplevel=False)
        vals = host_gather_column(col.values, entry_idx, _toplevel=False)
        return MapColumn(keys, vals, new_off, vpad, col.dtype)

    if isinstance(col, StructColumn):
        kids = tuple(host_gather_column(c, idx, _toplevel=False)
                     for c in col.children)
        return type(col)(kids, vpad, col.dtype)  # incl. Decimal128

    data = _np(col.data)[idx] if len(idx) else \
        np.zeros(0, _np(col.data).dtype)
    dpad = np.zeros(cap, data.dtype)
    dpad[: len(idx)] = data
    return Column(dpad, vpad, col.dtype)


def host_gather_batch(batch: ColumnarBatch, idx: np.ndarray
                      ) -> ColumnarBatch:
    cols = [host_gather_column(c, idx) for c in batch.columns]
    return ColumnarBatch(cols, len(idx), batch.schema)


# ---------------------------------------------------------------------------
# host row-range slice (partition emission without gathers)
# ---------------------------------------------------------------------------

def host_slice_column(col: Column, lo: int, hi: int) -> Column:
    """Rows [lo, hi) of a host-backed column as a compact column — the
    gather-free partition emission (ISSUE 9 satellite): offsets rebase
    by subtraction, validity/data/bytes copy as contiguous slices.
    Output arrays match host_gather_column(col, arange(lo, hi)) exactly
    (same capacity buckets, same padding), so serialized frames are
    byte-identical between the two paths."""
    n = hi - lo
    cap = bucket_capacity(max(n, 1))
    vpad = np.zeros(cap, np.bool_)
    vpad[:n] = _np(col.validity)[lo:hi]

    def _sliced_offsets(off: np.ndarray):
        base = int(off[lo])
        end = int(off[hi]) if n else base
        new_off = np.zeros(cap + 1, np.int32)
        new_off[: n + 1] = off[lo: hi + 1] - base
        new_off[n + 1:] = new_off[n]
        return new_off, base, end

    if isinstance(col, StringColumn):
        new_off, base, end = _sliced_offsets(_np(col.offsets))
        out = np.zeros(bucket_capacity(max(end - base, 1)), np.uint8)
        out[: end - base] = _np(col.data)[base:end]
        return StringColumn(out, new_off, vpad, col.dtype)

    if isinstance(col, ArrayColumn):
        new_off, base, end = _sliced_offsets(_np(col.offsets))
        child = host_slice_column(col.child, base, end)
        return ArrayColumn(child, new_off, vpad, col.dtype)

    if isinstance(col, MapColumn):
        new_off, base, end = _sliced_offsets(_np(col.offsets))
        keys = host_slice_column(col.keys, base, end)
        vals = host_slice_column(col.values, base, end)
        return MapColumn(keys, vals, new_off, vpad, col.dtype)

    if isinstance(col, StructColumn):
        kids = tuple(host_slice_column(c, lo, hi) for c in col.children)
        return type(col)(kids, vpad, col.dtype)  # incl. Decimal128

    data = _np(col.data)
    dpad = np.zeros(cap, data.dtype)
    dpad[:n] = data[lo:hi]
    return Column(dpad, vpad, col.dtype)


def host_slice_batch(batch: ColumnarBatch, lo: int, hi: int
                     ) -> ColumnarBatch:
    cols = [host_slice_column(c, lo, hi) for c in batch.columns]
    return ColumnarBatch(cols, hi - lo, batch.schema)
