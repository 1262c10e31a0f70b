"""ColumnarBatch — the unit of work flowing between operators.

TPU analog of Spark's ColumnarBatch of GpuColumnVector (reference
GpuColumnVector.java:40). Differences driven by XLA:

  * `num_rows` is carried as a *device* int32 scalar so that row-count-changing
    ops (filter, join) stay inside one compiled program. A host-side cached int
    is kept when statically known; reading `num_rows_host` on a traced batch
    forces a device sync (the analog of a cudaStreamSynchronize — use sparingly,
    operators should stay on device).
  * all columns share one capacity bucket; `sized_to` grows buckets so two
    batches can be processed by one compiled kernel shape.

The batch is a pytree: entire operator pipelines jit end-to-end.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..types import DataType, Schema, StringType, StructField
from .column import (
    Column, StringColumn, bucket_capacity, column_from_arrow, column_to_arrow,
)


class ColumnarBatch:
    __slots__ = ("columns", "num_rows", "schema", "_host_rows")

    def __init__(self, columns: Sequence[Column], num_rows, schema: Schema,
                 host_rows: Optional[int] = None):
        self.columns = tuple(columns)
        if isinstance(num_rows, (int, np.integer)):
            host_rows = int(num_rows)
            num_rows = jnp.asarray(num_rows, jnp.int32)
        self.num_rows = num_rows
        self.schema = schema
        self._host_rows = host_rows

    # -- accessors ---------------------------------------------------------
    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_rows_host(self) -> int:
        """Logical row count as a host int; syncs if produced on device."""
        if self._host_rows is None:
            from ..obs.dispatch import host_read
            self._host_rows = int(host_read("batch.num_rows", self.num_rows))
        return self._host_rows

    def column(self, name_or_idx) -> Column:
        if isinstance(name_or_idx, str):
            return self.columns[self.schema.index_of(name_or_idx)]
        return self.columns[name_or_idx]

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_pydict(data: dict, schema: Schema,
                    capacity: Optional[int] = None) -> "ColumnarBatch":
        lengths = {len(v) for v in data.values()} or {0}
        assert len(lengths) == 1, "ragged input columns"
        n = lengths.pop()
        cap = capacity or bucket_capacity(n)
        from .column import build_column
        cols = [build_column(data[f.name], f.data_type, cap)
                for f in schema.fields]
        return ColumnarBatch(cols, n, schema)

    @staticmethod
    def from_arrow(table, fault_key=None) -> "ColumnarBatch":
        """pyarrow Table/RecordBatch -> device batch (one capacity
        bucket). The scan ingest seam (ISSUE 10): the whole batch
        crosses the host->device boundary through the packed upload
        engine — ONE transfer per batch when
        `spark.rapids.tpu.transfer.packedUpload.enabled` (default), one
        per buffer otherwise. On the packed lane a fixed-width column
        whose Arrow buffer already holds the engine's representation is
        NOT built here: it rides as an `upload.ArrowFixed` and the
        packer writes it from the Arrow buffers straight into the
        staging buffer; every other column (strings, dictionaries,
        DECIMAL, BOOLEAN, nested, any type that needs a cast), and every
        column with the conf off, is built host-resident as before.
        `fault_key` is the batch's chaos work-item key (the scan chunk
        offset)."""
        from ..config import UPLOAD_PACKED, active_conf
        from ..obs import op_span
        from .column import host_build
        from .upload import ArrowFixed, to_device_batch
        n = table.num_rows
        cap = bucket_capacity(n)
        fields, cols = [], []
        with op_span("upload.pack", phase="upload"), host_build():
            packed = active_conf().get(UPLOAD_PACKED)
            for name in table.column_names:
                arr = table.column(name)
                col = ArrowFixed.of(arr, cap) if packed else None
                if col is None:
                    col = column_from_arrow(arr)
                    if col.capacity < cap:
                        col = col.with_capacity(cap)
                cols.append(col)
                fields.append(StructField(name, col.dtype))
        # ISSUE 18: account encoded vs decoded scan lanes (encoded_scan
        # event + advisor evidence) while buffers are still host numpy
        from .encoded import note_scan_batch
        note_scan_batch(cols)
        return to_device_batch(cols, n, Schema(tuple(fields)),
                               fault_key=fault_key, seam="scan")

    # -- host materialization ---------------------------------------------
    # All three fetch the whole batch as ONE packed d2h transfer
    # (columnar/transfer.py) — per-column fetches each pay a full device
    # round trip, which dominates everything else on remote-attached TPUs.
    def to_arrow(self):
        import pyarrow as pa
        from .transfer import fetch_batch_host
        cols, n = fetch_batch_host(self)
        self._host_rows = n
        arrays = [column_to_arrow(c, n) for c in cols]
        return pa.table(arrays, names=self.schema.names)

    def to_pydict(self) -> dict:
        from .transfer import fetch_batch_host
        cols, n = fetch_batch_host(self)
        self._host_rows = n
        return {f.name: c.to_pylist(n)
                for f, c in zip(self.schema.fields, cols)}

    def to_pylist(self) -> List[tuple]:
        d = self.to_pydict()
        names = self.schema.names
        n = self.num_rows_host
        return [tuple(d[name][i] for name in names) for i in range(n)]

    # -- shape management --------------------------------------------------
    def sized_to(self, capacity: int) -> "ColumnarBatch":
        if capacity == self.capacity:
            return self
        return ColumnarBatch([c.with_capacity(capacity) for c in self.columns],
                             self.num_rows if self._host_rows is None
                             else self._host_rows,
                             self.schema, self._host_rows)

    def with_columns(self, columns: Sequence[Column],
                     schema: Schema) -> "ColumnarBatch":
        return ColumnarBatch(columns, self.num_rows if self._host_rows is None
                             else self._host_rows, schema, self._host_rows)

    def device_size_bytes(self) -> int:
        """Padded physical footprint (capacity-based, like cuDF deviceMemorySize)."""
        total = 0
        for c in jax.tree_util.tree_leaves(self):
            total += int(np.prod(c.shape)) * c.dtype.itemsize if hasattr(c, "dtype") else 0
        return total

    def __repr__(self):
        rows = self._host_rows if self._host_rows is not None else "<traced>"
        return f"ColumnarBatch(rows={rows}, cap={self.capacity}, schema={self.schema.names})"


def _batch_flatten(b: ColumnarBatch):
    return (b.columns, b.num_rows), b.schema


def _batch_unflatten(schema, children):
    cols, num_rows = children
    return ColumnarBatch(cols, num_rows, schema)


jax.tree_util.register_pytree_node(ColumnarBatch, _batch_flatten, _batch_unflatten)


def committed_device(batch: ColumnarBatch):
    """The one device a batch's arrays are committed to, or None."""
    leaves = jax.tree_util.tree_leaves(batch.columns[:1])
    if not leaves or not getattr(leaves[0], "committed", False):
        return None
    devs = leaves[0].devices()
    return next(iter(devs)) if len(devs) == 1 else None


def empty_batch(schema: Schema, capacity: int = 128) -> ColumnarBatch:
    from .column import build_column
    cols = [build_column([], f.data_type, capacity)
            for f in schema.fields]
    return ColumnarBatch(cols, 0, schema)
