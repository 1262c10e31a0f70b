"""Device-resident columnar vectors — the TPU analog of the reference's
GpuColumnVector (sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java:40)
over ai.rapids.cudf.ColumnVector.

Design (TPU-first, NOT a cuDF translation):
  * XLA requires static shapes, so every column is padded to a *capacity
    bucket* (powers of two, >= 128 to match TPU lane width). The logical row
    count rides next to the data as a device scalar so that filters/joins that
    change row counts do NOT change array shapes and therefore do NOT trigger
    recompilation. This replaces cuDF's exact-length device buffers.
  * Validity is a dense bool array (not a bitmask): TPUs are vector machines,
    predication via bool arrays fuses into elementwise ops for free, and XLA
    packs bools on device. Rows at index >= num_rows are always invalid.
  * Strings/binary use Arrow-style (offsets, bytes) twin arrays with the byte
    buffer padded to its own bucket. There is no ragged tensor support in XLA;
    all varlen kernels are written against this encoding.
  * Columns are registered pytrees, so whole query pipelines (chains of
    operators) jit end-to-end and XLA fuses across operator boundaries —
    something the reference could never do across separate cuDF calls.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (
    ArrayType, BinaryType, BooleanType, DataType, DecimalType, NullType,
    Schema, StringType, StructField, StructType, from_arrow, to_arrow,
)

#: minimum capacity bucket — one TPU lane row
MIN_BUCKET = 128

#: host-build mode (ISSUE 10): inside `host_build()` every constructor
#: lane keeps its buffers as numpy instead of uploading them one
#: jnp.asarray at a time, so the packed upload engine
#: (columnar/upload.py) can ship the whole batch as ONE transfer
_BUILD_TLS = threading.local()


def _dev(x):
    """Constructor-lane leaf placement: device by default, numpy under
    host_build() (the packed-upload staging mode)."""
    if getattr(_BUILD_TLS, "host", False):
        return x if isinstance(x, np.ndarray) else np.asarray(x)
    return jnp.asarray(x)


@contextmanager
def host_build():
    """Build columns with numpy-resident buffers (no per-buffer device
    uploads); promote the finished batch through columnar/upload.py."""
    prev = getattr(_BUILD_TLS, "host", False)
    _BUILD_TLS.host = True
    try:
        yield
    finally:
        _BUILD_TLS.host = prev


def _pad_tail(arr, extra: int):
    """Zero-pad the leading axis by `extra` slots, staying numpy for
    numpy inputs (host-built columns must not silently hop to device)."""
    pad = [(0, extra)] + [(0, 0)] * (arr.ndim - 1)
    if isinstance(arr, np.ndarray):
        return np.pad(arr, pad)
    return jnp.pad(arr, pad)


def _extend_offsets(off, extra: int):
    """Repeat the final offset `extra` times (zero-length padding rows),
    numpy-in numpy-out."""
    if isinstance(off, np.ndarray):
        return np.concatenate([off, np.full(extra, off[-1], off.dtype)])
    return jnp.concatenate(
        [off, jnp.broadcast_to(off[-1], (extra,))])


def _logical_to_physical(dtype: DataType):
    """Value converter for host ingestion: accept the *logical* Python
    values Spark's rows carry (datetime.date, datetime.datetime,
    decimal.Decimal) alongside the raw physical encodings (int days /
    micros / unscaled)."""
    import datetime as _dt
    import decimal as _dec

    from ..types import DateType, DecimalType, TimestampNTZType, TimestampType
    if isinstance(dtype, DateType):
        epoch = _dt.date(1970, 1, 1)
        return lambda v: (v - epoch).days if isinstance(v, _dt.date) \
            and not isinstance(v, _dt.datetime) else v
    if isinstance(dtype, (TimestampType, TimestampNTZType)):
        epoch = _dt.datetime(1970, 1, 1)
        one_us = _dt.timedelta(microseconds=1)
        ntz = isinstance(dtype, TimestampNTZType)

        def conv_ts(v):
            if not isinstance(v, _dt.datetime):
                return v
            if v.tzinfo is not None:
                # NTZ keeps the wall clock; TIMESTAMP converts the instant
                v = v.replace(tzinfo=None) if ntz \
                    else v.astimezone(_dt.timezone.utc).replace(tzinfo=None)
            return (v - epoch) // one_us
        return conv_ts
    if isinstance(dtype, DecimalType):
        scale = dtype.scale
        return lambda v: int(v.scaleb(scale).to_integral_value(
            rounding=_dec.ROUND_HALF_UP)) \
            if isinstance(v, _dec.Decimal) else v
    return lambda v: v


def bucket_capacity(n: int) -> int:
    """Round row/byte counts up to a shape bucket to bound XLA recompiles.

    Replaces the reference's exact-size allocations; the 1 GiB target batch
    size of the reference (RapidsConf.scala:559 batchSizeBytes) becomes a
    target *padded* bucket here.
    """
    if n <= MIN_BUCKET:
        return MIN_BUCKET
    return 1 << (int(n - 1).bit_length())


def _host(*leaves) -> tuple:
    """The host copies of a column's leaves, for `to_pylist`: device
    leaves in ONE read through the host-read door (site
    `column.to_pylist`), numpy leaves as they are."""
    from ..obs.dispatch import host_read
    return tuple(np.asarray(x) for x in host_read("column.to_pylist", leaves))


def _pad_np(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    if arr.shape[0] == capacity:
        return arr
    out = np.full((capacity,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class Column:
    """Fixed-width device column: data (capacity,) + validity (capacity,) bool."""

    __slots__ = ("data", "validity", "dtype")

    def __init__(self, data, validity, dtype: DataType):
        self.data = data
        self.validity = validity
        self.dtype = dtype

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_numpy(values: np.ndarray, dtype: DataType,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "Column":
        n = values.shape[0]
        cap = capacity or bucket_capacity(n)
        if validity is None:
            validity = np.ones(n, dtype=np.bool_)
        data = _pad_np(np.ascontiguousarray(values, dtype=dtype.jnp_dtype), cap)
        valid = _pad_np(validity.astype(np.bool_), cap, fill=False)
        return Column(_dev(data), _dev(valid), dtype)

    @staticmethod
    def from_pylist(values: Sequence, dtype: DataType,
                    capacity: Optional[int] = None) -> "Column":
        n = len(values)
        validity = np.array([v is not None for v in values], dtype=np.bool_)
        fill = np.zeros((), dtype=dtype.jnp_dtype).item()
        conv = _logical_to_physical(dtype)
        dense = np.array([fill if v is None else conv(v) for v in values],
                         dtype=dtype.jnp_dtype)
        return Column.from_numpy(dense, dtype, validity, capacity)

    # -- shape -------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def with_capacity(self, capacity: int) -> "Column":
        """Grow (never shrink) the padding bucket."""
        cap = self.capacity
        if capacity == cap:
            return self
        assert capacity > cap, (capacity, cap)
        extra = capacity - cap
        return Column(_pad_tail(self.data, extra),
                      _pad_tail(self.validity, extra), self.dtype)

    # -- host materialization (test/debug surface) -------------------------
    def to_pylist(self, num_rows: int) -> List:
        data, valid = _host(self.data[:num_rows], self.validity[:num_rows])
        return [data[i].item() if valid[i] else None for i in range(num_rows)]

    def __repr__(self):
        return f"Column({self.dtype!r}, cap={self.capacity})"


class StringColumn(Column):
    """Varlen column: uint8 byte buffer + int32 offsets (Arrow layout).

    offsets has shape (capacity+1,); for rows >= num_rows offsets repeat so
    lengths are zero. The byte buffer is padded to its own bucket.
    """

    __slots__ = ("offsets",)

    def __init__(self, data, offsets, validity, dtype: DataType = StringType()):
        super().__init__(data, validity, dtype)
        self.offsets = offsets

    @staticmethod
    def from_pylist(values: Sequence[Optional[str]],
                    capacity: Optional[int] = None,
                    dtype: DataType = StringType()) -> "StringColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        raw = [b"" if v is None else (v.encode("utf-8") if isinstance(v, str) else bytes(v))
               for v in values]
        lengths = np.array([len(b) for b in raw], dtype=np.int32)
        offsets = np.zeros(cap + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1 : n + 1])
        offsets[n + 1 :] = offsets[n]
        total = int(offsets[n])
        byte_cap = bucket_capacity(max(total, 1))
        data = np.zeros(byte_cap, dtype=np.uint8)
        if total:
            data[:total] = np.frombuffer(b"".join(raw), dtype=np.uint8)
        validity = _pad_np(np.array([v is not None for v in values], dtype=np.bool_),
                           cap, fill=False)
        return StringColumn(_dev(data), _dev(offsets), _dev(validity), dtype)

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def byte_capacity(self) -> int:
        return int(self.data.shape[0])

    def with_capacity(self, capacity: int) -> "StringColumn":
        cap = self.capacity
        if capacity == cap:
            return self
        assert capacity > cap
        extra = capacity - cap
        offsets = _extend_offsets(self.offsets, extra)
        validity = _pad_tail(self.validity, extra)
        return StringColumn(self.data, offsets, validity, self.dtype)

    def with_byte_capacity(self, byte_capacity: int) -> "StringColumn":
        """Grow (never shrink) the byte-buffer bucket."""
        if byte_capacity == self.byte_capacity:
            return self
        assert byte_capacity > self.byte_capacity
        data = _pad_tail(self.data, byte_capacity - self.byte_capacity)
        return StringColumn(data, self.offsets, self.validity, self.dtype)

    def to_pylist(self, num_rows: int) -> List[Optional[str]]:
        data, offsets, valid = _host(self.data, self.offsets, self.validity)
        out: List[Optional[str]] = []
        binary = isinstance(self.dtype, BinaryType)
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
            else:
                b = data[offsets[i] : offsets[i + 1]].tobytes()
                out.append(b if binary else b.decode("utf-8"))
        return out

    def __repr__(self):
        return f"StringColumn(cap={self.capacity}, bytes={self.byte_capacity})"


class StructColumn(Column):
    """Struct column: children stored side by side; no data buffer of its own."""

    __slots__ = ("children",)

    def __init__(self, children: Tuple[Column, ...], validity, dtype: StructType):
        super().__init__(None, validity, dtype)
        self.children = tuple(children)

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    def with_capacity(self, capacity: int) -> "StructColumn":
        """Grow (never shrink) the padding bucket, recursing into the
        children; type(self)(...) keeps Decimal128Column intact."""
        cap = self.capacity
        if capacity == cap:
            return self
        assert capacity > cap, (capacity, cap)
        return type(self)(tuple(c.with_capacity(capacity)
                                for c in self.children),
                          _pad_tail(self.validity, capacity - cap),
                          self.dtype)

    @staticmethod
    def from_pylist(values: Sequence, dtype: StructType,
                    capacity: Optional[int] = None) -> "StructColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = _pad_np(np.array([v is not None for v in values],
                                    np.bool_), cap, False)
        kids = []
        for f in dtype.fields:
            fv = [None if v is None else
                  (v.get(f.name) if isinstance(v, dict)
                   else getattr(v, f.name)) for v in values]
            kids.append(build_column(fv, f.data_type, cap))
        return StructColumn(tuple(kids), _dev(validity), dtype)

    def to_pylist(self, num_rows: int) -> List:
        (valid,) = _host(self.validity[:num_rows])
        kids = [c.to_pylist(num_rows) for c in self.children]
        names = [f.name for f in self.dtype.fields]
        return [
            {n: k[i] for n, k in zip(names, kids)} if valid[i] else None
            for i in range(num_rows)
        ]


class Decimal128Column(StructColumn):
    """DECIMAL(p>18): 128-bit unscaled value as two int64 limb children
    (hi with the sign, lo reinterpreted unsigned). Subclasses
    StructColumn so every structural path (gather/sanitize/transfer/
    serialize) recurses into the limbs unchanged; reconstruction sites
    rebuild via type(col)(...) so the class is preserved.
    Reference analog: cuDF decimal128 under DecimalUtil.scala."""

    def __init__(self, children, validity, dtype: DecimalType):
        assert len(children) == 2
        super().__init__(children, validity, dtype)

    @property
    def hi(self) -> Column:
        return self.children[0]

    @property
    def lo(self) -> Column:
        return self.children[1]

    @staticmethod
    def from_limbs(hi, lo, validity, dtype: DecimalType
                   ) -> "Decimal128Column":
        from ..types import LONG
        return Decimal128Column(
            (Column(hi, validity, LONG), Column(lo, validity, LONG)),
            validity, dtype)

    @staticmethod
    def from_pylist(values: Sequence, dtype: DecimalType,
                    capacity: Optional[int] = None) -> "Decimal128Column":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        conv = _logical_to_physical(dtype)
        validity = np.array([v is not None for v in values], np.bool_)
        his = np.zeros(n, np.int64)
        los = np.zeros(n, np.int64)
        for i, v in enumerate(values):
            if v is None:
                continue
            u = int(conv(v)) & ((1 << 128) - 1)
            lo = u & ((1 << 64) - 1)
            hi = u >> 64
            los[i] = lo - (1 << 64) if lo >= (1 << 63) else lo
            his[i] = hi - (1 << 64) if hi >= (1 << 63) else hi
        vpad = _dev(_pad_np(validity, cap, False))
        from ..types import LONG
        return Decimal128Column(
            (Column(_dev(_pad_np(his, cap)), vpad, LONG),
             Column(_dev(_pad_np(los, cap)), vpad, LONG)),
            vpad, dtype)

    def to_pylist(self, num_rows: int) -> List:
        """Unscaled 128-bit ints (arbitrary-precision Python ints)."""
        hi, lo, valid = _host(self.hi.data[:num_rows], self.lo.data[:num_rows],
                              self.validity[:num_rows])
        out: List = []
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
                continue
            u = ((int(hi[i]) & ((1 << 64) - 1)) << 64) \
                | (int(lo[i]) & ((1 << 64) - 1))
            out.append(u - (1 << 128) if u >= (1 << 127) else u)
        return out


class ArrayColumn(Column):
    """List column: int32 offsets into a child column."""

    __slots__ = ("offsets", "child")

    def __init__(self, child: Column, offsets, validity, dtype: ArrayType):
        super().__init__(None, validity, dtype)
        self.child = child
        self.offsets = offsets

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def child_capacity(self) -> int:
        return self.child.capacity

    @staticmethod
    def from_pylist(values: Sequence, dtype: ArrayType,
                    capacity: Optional[int] = None) -> "ArrayColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = _pad_np(np.array([v is not None for v in values],
                                    np.bool_), cap, False)
        lengths = np.array([0 if v is None else len(v) for v in values],
                           np.int32)
        off = np.zeros(cap + 1, np.int32)
        np.cumsum(lengths, out=off[1:n + 1])
        off[n + 1:] = off[n] if n else 0
        flat = [x for v in values if v is not None for x in v]
        child = build_column(flat, dtype.element_type)
        return ArrayColumn(child, _dev(off), _dev(validity), dtype)

    def to_pylist(self, num_rows: int) -> List:
        offsets, valid = _host(self.offsets, self.validity[:num_rows])
        child_n = int(offsets[num_rows]) if num_rows else 0
        kid = self.child.to_pylist(child_n)
        return [
            kid[offsets[i] : offsets[i + 1]] if valid[i] else None
            for i in range(num_rows)
        ]


class MapColumn(Column):
    """Map column: int32 offsets + parallel keys/values child columns
    (the cuDF lists-of-structs layout with the struct unzipped — keys and
    values as SEPARATE columns vectorize lookups without interleaving).
    Reference analog: cuDF LIST<STRUCT<K,V>> under GpuCreateMap /
    GpuGetMapValue (collectionOperations.scala, GpuMapUtils)."""

    __slots__ = ("offsets", "keys", "values")

    def __init__(self, keys: Column, values: Column, offsets, validity,
                 dtype):
        super().__init__(None, validity, dtype)
        self.keys = keys
        self.values = values
        self.offsets = offsets

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def entry_capacity(self) -> int:
        return self.keys.capacity

    @staticmethod
    def from_pylist(values: Sequence, dtype,
                    capacity: Optional[int] = None) -> "MapColumn":
        n = len(values)
        cap = capacity or bucket_capacity(n)
        validity = _pad_np(np.array([v is not None for v in values],
                                    np.bool_), cap, False)
        lengths = np.array([0 if v is None else len(v) for v in values],
                           np.int32)
        off = np.zeros(cap + 1, np.int32)
        np.cumsum(lengths, out=off[1:n + 1])
        off[n + 1:] = off[n] if n else 0
        items = [(k, x) for v in values if v is not None
                 for k, x in (v.items() if isinstance(v, dict) else v)]
        keys = build_column([k for k, _ in items], dtype.key_type)
        vals = build_column([x for _, x in items], dtype.value_type)
        # keys and values index in lockstep by construction
        assert keys.capacity == vals.capacity
        return MapColumn(keys, vals, _dev(off), _dev(validity), dtype)

    def with_capacity(self, capacity: int) -> "MapColumn":
        cap = self.capacity
        if capacity == cap:
            return self
        assert capacity > cap, (capacity, cap)
        extra = capacity - cap
        offsets = _extend_offsets(self.offsets, extra)
        validity = _pad_tail(self.validity, extra)
        return MapColumn(self.keys, self.values, offsets, validity,
                         self.dtype)

    def to_pylist(self, num_rows: int) -> List:
        offsets, valid = _host(self.offsets, self.validity[:num_rows])
        entry_n = int(offsets[num_rows]) if num_rows else 0
        ks = self.keys.to_pylist(entry_n)
        vs = self.values.to_pylist(entry_n)
        out = []
        for i in range(num_rows):
            if not valid[i]:
                out.append(None)
                continue
            d = {}
            for k, v in zip(ks[offsets[i]: offsets[i + 1]],
                            vs[offsets[i]: offsets[i + 1]]):
                if k not in d:  # FIRST duplicate key wins, like map_get
                    d[k] = v
            out.append(d)
        return out


def build_column(values: Sequence, dtype: DataType,
                 capacity: Optional[int] = None) -> Column:
    """Host-list → column of the right class for any supported type,
    recursing through nested arrays/structs/maps."""
    from ..types import MapType
    if isinstance(dtype, DecimalType) and dtype.precision > 18:
        return Decimal128Column.from_pylist(values, dtype, capacity)
    if isinstance(dtype, ArrayType):
        return ArrayColumn.from_pylist(values, dtype, capacity)
    if isinstance(dtype, MapType):
        return MapColumn.from_pylist(values, dtype, capacity)
    if isinstance(dtype, StructType):
        return StructColumn.from_pylist(values, dtype, capacity)
    if isinstance(dtype, StringType) or dtype.jnp_dtype is None:
        return StringColumn.from_pylist(values, capacity, dtype=dtype)
    return Column.from_pylist(values, dtype, capacity)


# --- pytree registration: columns flow through jit/shard_map -------------

def _column_flatten(c: Column):
    return (c.data, c.validity), c.dtype


def _column_unflatten(dtype, children):
    data, validity = children
    return Column(data, validity, dtype)


def _string_flatten(c: StringColumn):
    return (c.data, c.offsets, c.validity), c.dtype


def _string_unflatten(dtype, children):
    data, offsets, validity = children
    return StringColumn(data, offsets, validity, dtype)


def _struct_flatten(c: StructColumn):
    return (c.children, c.validity), c.dtype


def _struct_unflatten(dtype, children):
    kids, validity = children
    return StructColumn(tuple(kids), validity, dtype)


def _array_flatten(c: ArrayColumn):
    return (c.child, c.offsets, c.validity), c.dtype


def _array_unflatten(dtype, children):
    child, offsets, validity = children
    return ArrayColumn(child, offsets, validity, dtype)


def _map_flatten(c: MapColumn):
    return (c.keys, c.values, c.offsets, c.validity), c.dtype


def _map_unflatten(dtype, children):
    keys, values, offsets, validity = children
    return MapColumn(keys, values, offsets, validity, dtype)


def _dec128_unflatten(dtype, children):
    kids, validity = children
    return Decimal128Column(tuple(kids), validity, dtype)


jax.tree_util.register_pytree_node(Column, _column_flatten, _column_unflatten)
jax.tree_util.register_pytree_node(StringColumn, _string_flatten, _string_unflatten)
jax.tree_util.register_pytree_node(StructColumn, _struct_flatten, _struct_unflatten)
jax.tree_util.register_pytree_node(ArrayColumn, _array_flatten, _array_unflatten)
jax.tree_util.register_pytree_node(MapColumn, _map_flatten, _map_unflatten)
jax.tree_util.register_pytree_node(Decimal128Column, _struct_flatten,
                                   _dec128_unflatten)


def _string_from_arrow_buffers(arr, dt: DataType, n: int) -> StringColumn:
    """Arrow string/binary array -> device column straight from the Arrow
    (validity bitmap, offsets, bytes) buffers — no per-value Python loop
    (review finding r1: `to_pylist` dominated string-heavy scans).

    ISSUE 10 satellite: offsets and data each copy out of the Arrow
    snapshot exactly ONCE, straight into their padded buffers (the old
    lane materialized offsets twice — astype then rebase — before the
    padded copy, a host-side double-copy on every string scan batch)."""
    import pyarrow as pa

    if pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.string())
    elif pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.binary())
    bufs = arr.buffers()
    # ONE zero-copy snapshot of the Arrow offsets; the single copy below
    # lands them in the padded buffer, where the rebase runs in place
    off_all = np.frombuffer(bufs[1], dtype=np.int32)
    cap = bucket_capacity(n)
    off_padded = np.empty(cap + 1, dtype=np.int32)
    off_padded[: n + 1] = off_all[arr.offset: arr.offset + n + 1]
    base = int(off_padded[0]) if n else 0
    if base:
        off_padded[: n + 1] -= base
    total = int(off_padded[n]) if n else 0
    off_padded[n + 1:] = total
    byte_cap = bucket_capacity(max(total, 1))
    data = np.zeros(byte_cap, dtype=np.uint8)
    if total:
        # ONE copy out of the shared bytes snapshot (frombuffer is a view)
        data[:total] = np.frombuffer(bufs[2], dtype=np.uint8,
                                     count=total, offset=base)
    if bufs[0] is None:
        validity = np.ones(n, dtype=np.bool_)
    else:
        bits = np.frombuffer(bufs[0], dtype=np.uint8)
        validity = np.unpackbits(bits, bitorder="little")[
            arr.offset: arr.offset + n].astype(np.bool_)
    # Arrow permits null slots with non-zero spans; the engine's length
    # kernels promise 0 for nulls — rebuild through the slow path in that
    # (rare in practice) case
    if n and not validity.all():
        lens_np = np.diff(off_padded[: n + 1])
        if (lens_np[~validity] != 0).any():
            return StringColumn.from_pylist(arr.to_pylist(), dtype=dt)
    return StringColumn(_dev(data), _dev(off_padded),
                        _dev(_pad_np(validity, cap, False)), dt)


def column_from_arrow(arr, dtype: Optional[DataType] = None) -> Column:
    """pyarrow Array/ChunkedArray -> device column."""
    import pyarrow as pa

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        # ISSUE 18: keep Parquet dictionary columns encoded (a
        # DictionaryColumn code lane + payload) instead of eagerly
        # decoding to full width; conf off or unencodable shapes
        # (non-string values, nulls in the dictionary) decode eagerly.
        from ..config import SCAN_ENCODED, active_conf
        if active_conf().get(SCAN_ENCODED):
            from .encoded import dictionary_from_arrow
            dt = dtype or from_arrow(arr.type.value_type)
            if isinstance(dt, (StringType, BinaryType)):
                enc = dictionary_from_arrow(arr, dt)
                if enc is not None:
                    return enc
        arr = arr.dictionary_decode()
    dt = dtype or from_arrow(arr.type)
    n = len(arr)
    if isinstance(dt, (StringType, BinaryType)):
        return _string_from_arrow_buffers(arr, dt, n)
    if isinstance(dt, StructType):
        validity = np.asarray(arr.is_valid())
        kids = tuple(column_from_arrow(arr.field(i), f.data_type)
                     for i, f in enumerate(dt.fields))
        cap = bucket_capacity(n)
        return StructColumn(kids, _dev(_pad_np(validity, cap, False)), dt)
    if isinstance(dt, ArrayType):
        validity = np.asarray(arr.is_valid())
        offsets = np.asarray(arr.offsets, dtype=np.int32)
        cap = bucket_capacity(n)
        off = np.zeros(cap + 1, dtype=np.int32)
        off[: n + 1] = offsets
        off[n + 1 :] = offsets[n] if n else 0
        child = column_from_arrow(arr.values, dt.element_type)
        return ArrayColumn(child, _dev(off),
                           _dev(_pad_np(validity, cap, False)), dt)
    from ..types import MapType as _MapType
    if isinstance(dt, _MapType):
        validity = np.asarray(arr.is_valid())
        offsets = np.asarray(arr.offsets, dtype=np.int32)
        cap = bucket_capacity(n)
        off = np.zeros(cap + 1, dtype=np.int32)
        off[: n + 1] = offsets
        off[n + 1:] = offsets[n] if n else 0
        keys = column_from_arrow(arr.keys, dt.key_type)
        vals = column_from_arrow(arr.items, dt.value_type)
        assert keys.capacity == vals.capacity  # same entry count
        return MapColumn(keys, vals, _dev(off),
                         _dev(_pad_np(validity, cap, False)), dt)
    if isinstance(dt, NullType):
        cap = bucket_capacity(max(n, 1))
        return Column(_dev(np.zeros(cap, np.int8)),
                      _dev(np.zeros(cap, np.bool_)), dt)
    if isinstance(dt, DecimalType):
        pylist = arr.to_pylist()
        if dt.precision > 18:
            return Decimal128Column.from_pylist(pylist, dt)
        unscaled = np.array(
            [0 if v is None else int(round(v.scaleb(dt.scale)))
             for v in pylist], dtype=np.int64)
        validity = np.array([v is not None for v in pylist], dtype=np.bool_)
        return Column.from_numpy(unscaled, dt, validity)
    if isinstance(dt, BooleanType):
        validity = np.asarray(arr.is_valid())
        dense = np.asarray(arr.fill_null(False), dtype=np.bool_)
        return Column.from_numpy(dense, dt, validity)
    validity = np.asarray(arr.is_valid())
    dense = np.asarray(arr.fill_null(0))
    return Column.from_numpy(dense.astype(dt.jnp_dtype), dt, validity)


def column_to_arrow(col: Column, num_rows: int):
    """Device column -> pyarrow array (host materialization)."""
    import pyarrow as pa

    dt = col.dtype
    if isinstance(dt, DecimalType):
        # both tiers (int64 and two-limb) surface unscaled Python ints
        vals = col.to_pylist(num_rows)
        import decimal as _d
        scaled = [None if v is None else _d.Decimal(v).scaleb(-dt.scale) for v in vals]
        return pa.array(scaled, type=to_arrow(dt))
    return pa.array(col.to_pylist(num_rows), type=to_arrow(dt))
