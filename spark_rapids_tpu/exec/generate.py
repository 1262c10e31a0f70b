"""GenerateExec — explode/posexplode over array columns (reference
GpuGenerateExec.scala:829: GpuExplode/GpuPosExplode generators with
outer/position variants).

TPU shape strategy: the output capacity is the array child's static
capacity bucket (every element becomes at most one row) plus the input
capacity for the outer variant — so the whole generate is ONE compiled
program per batch shape with no host sync at all."""

from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import (ArrayColumn, Column, MapColumn,
                               bucket_capacity)
from ..expr.core import Expression, resolve
from ..ops.basic import active_mask, compaction_order, gather_column
from ..types import ArrayType, IntegerType, Schema, StructField
from ..obs.dispatch import host_read, instrument
from .base import (DEBUG, DISPATCH_METRICS, NUM_INPUT_BATCHES, OP_TIME,
                   TpuExec)


class GenerateExec(TpuExec):
    def __init__(self, generator: Expression, child: TpuExec,
                 outer: bool = False, position: bool = False,
                 elem_name: str = "col", pos_name: str = "pos"):
        super().__init__(child)
        self.generator = generator
        self.outer = outer
        self.position = position
        self.elem_name = elem_name
        self.pos_name = pos_name
        self._bound = resolve(generator, child.output_schema)
        arr_t = self._bound.data_type
        from ..types import MapType
        self._is_map = isinstance(arr_t, MapType)
        if self._is_map:
            # explode(map) emits (key, value) pairs (reference
            # GpuGenerateExec.scala:829 map explode)
            self._key_type = arr_t.key_type
            self._elem_type = arr_t.value_type
        else:
            assert isinstance(arr_t, ArrayType), \
                f"explode needs an ARRAY or MAP input, got {arr_t}"
            self._elem_type = arr_t.element_type
        self._jit = instrument(self._kernel,
                               label="GenerateExec.explode", owner=self,
                               static_argnums=(1,))
        self._jit_measure = instrument(self._measure_kernel,
                                       label="GenerateExec.measure",
                                       owner=self)

    @property
    def output_schema(self) -> Schema:
        fields = list(self.child.output_schema.fields)
        if self.position:
            fields.append(StructField(self.pos_name, IntegerType(),
                                      self.outer))
        if self._is_map:
            fields.append(StructField("key", self._key_type, self.outer))
            fields.append(StructField("value", self._elem_type, True))
        else:
            fields.append(StructField(self.elem_name, self._elem_type,
                                      True))
        return Schema(tuple(fields))

    def additional_metrics(self):
        return ((NUM_INPUT_BATCHES, DEBUG),) + DISPATCH_METRICS

    def _measure_kernel(self, batch: ColumnarBatch):
        """Exact output payload need per variable-size payload column
        (explode DUPLICATES each row once per array element — the input's
        static byte bucket overflows silently otherwise, same hazard the
        joins measure away). One host sync per batch."""
        from ..columnar.column import StringColumn
        from ..ops.collection import array_lengths
        from ..ops.strings import string_lengths
        arr = self._bound.columnar_eval(batch)
        lens = array_lengths(arr).astype(jnp.int64)
        act = active_mask(batch.num_rows, batch.capacity)
        copies = jnp.where(act & arr.validity, lens, 0)
        if self.outer:
            empty = act & ((lens == 0) | ~arr.validity)
            copies = copies + jnp.where(empty, 1, 0)
        needs = []
        for c in batch.columns:
            if isinstance(c, StringColumn):
                sl = jnp.where(act, string_lengths(c), 0).astype(jnp.int64)
                needs.append(jnp.sum(copies * sl))
            elif isinstance(c, ArrayColumn):
                al = jnp.where(act, array_lengths(c), 0).astype(jnp.int64)
                needs.append(jnp.sum(copies * al))
                if isinstance(c.child, StringColumn):
                    # per-row child BYTE span for nested sizing
                    row_bytes = (c.child.offsets[c.offsets[1:]]
                                 - c.child.offsets[c.offsets[:-1]]
                                 ).astype(jnp.int64)
                    needs.append(jnp.sum(
                        copies * jnp.where(act, row_bytes, 0)))
            elif isinstance(c, MapColumn):
                el = jnp.where(act, c.offsets[1:] - c.offsets[:-1],
                               0).astype(jnp.int64)
                needs.append(jnp.sum(copies * el))
                for side in (c.keys, c.values):
                    if isinstance(side, StringColumn):
                        row_bytes = (side.offsets[c.offsets[1:]]
                                     - side.offsets[c.offsets[:-1]]
                                     ).astype(jnp.int64)
                        needs.append(jnp.sum(
                            copies * jnp.where(act, row_bytes, 0)))
        return tuple(needs)

    def _payload_caps(self, batch: ColumnarBatch) -> tuple:
        from ..columnar.column import StringColumn
        if not any(isinstance(c, (StringColumn, ArrayColumn, MapColumn))
                   for c in batch.columns):
            return (None,) * len(batch.columns)
        needs = iter(int(n) for n in host_read(
            "generate.payload", self._jit_measure(batch)))
        caps = []
        for c in batch.columns:
            if isinstance(c, StringColumn):
                caps.append(bucket_capacity(max(next(needs), 8)))
            elif isinstance(c, ArrayColumn):
                elems = bucket_capacity(max(next(needs), 8))
                if isinstance(c.child, StringColumn):
                    caps.append((elems,
                                 bucket_capacity(max(next(needs), 8))))
                else:
                    caps.append(elems)
            elif isinstance(c, MapColumn):
                elems = bucket_capacity(max(next(needs), 8))
                kb = bucket_capacity(max(next(needs), 8)) \
                    if isinstance(c.keys, StringColumn) else None
                vb = bucket_capacity(max(next(needs), 8)) \
                    if isinstance(c.values, StringColumn) else None
                caps.append((elems, kb, vb))
            else:
                caps.append(None)
        return tuple(caps)

    def _kernel(self, batch: ColumnarBatch, payload_caps: tuple = ()
                ) -> ColumnarBatch:
        arr = self._bound.columnar_eval(batch)
        from ..columnar.column import MapColumn
        if isinstance(arr, MapColumn):
            from ..ops.maps import map_keys
            map_col, arr = arr, map_keys(arr)  # offsets/validity vehicle
        else:
            map_col = None
            assert isinstance(arr, ArrayColumn)
        cap = batch.capacity
        child_cap = arr.child_capacity
        lens = arr.offsets[1:] - arr.offsets[:-1]
        act_rows = active_mask(batch.num_rows, cap)

        # elements to emit: inside the byte span of an ACTIVE, NON-NULL
        # row (computed arrays — e.g. CreateArray — carry element slots
        # for inactive/null rows too; compact those away)
        e_all = jnp.arange(child_cap, dtype=jnp.int32)
        row_all = jnp.clip(
            jnp.searchsorted(arr.offsets, e_all, side="right")
            .astype(jnp.int32) - 1, 0, cap - 1)
        keep = (e_all < arr.offsets[-1]) & act_rows[row_all] \
            & arr.validity[row_all]
        perm, total = compaction_order(keep, jnp.int32(child_cap))

        out_cap = bucket_capacity(child_cap + (cap if self.outer else 0))
        slots = jnp.arange(out_cap, dtype=jnp.int32)
        e = perm[jnp.clip(slots, 0, child_cap - 1)]
        e = jnp.clip(e, 0, child_cap - 1)
        src_row_of_elem = row_all[e]
        intra = e - arr.offsets[src_row_of_elem]
        is_elem = slots < total

        if self.outer:
            empty = act_rows & ((lens == 0) | ~arr.validity)
            empty_perm, n_empty = compaction_order(empty, batch.num_rows)
            k = jnp.clip(slots - total, 0, cap - 1)
            outer_row = jnp.where((slots >= total)
                                  & (slots < total + n_empty),
                                  empty_perm[k], -1)
            n_out = total + n_empty
        else:
            outer_row = jnp.full((out_cap,), -1, jnp.int32)
            n_out = total

        src_row = jnp.where(is_elem, src_row_of_elem, outer_row)
        act_out = active_mask(n_out, out_cap)
        src_row = jnp.where(act_out, src_row, -1)
        caps = payload_caps or (None,) * len(batch.columns)
        cols = [gather_column(c, src_row, out_byte_capacity=bc)
                for c, bc in zip(batch.columns, caps)]
        if self.position:
            pos_valid = is_elem & act_out
            cols.append(Column(jnp.where(pos_valid, intra, 0),
                               pos_valid if self.outer
                               else jnp.where(act_out, True, False),
                               IntegerType()))
        elem_idx = jnp.where(is_elem & act_out, e, -1)
        if map_col is not None:
            cols.append(gather_column(map_col.keys, elem_idx))
            cols.append(gather_column(map_col.values, elem_idx))
        else:
            cols.append(gather_column(arr.child, elem_idx))
        return ColumnarBatch(cols, n_out, self.output_schema)

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        op_time = self.metrics[OP_TIME]
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        for batch in self.child.execute():
            in_batches.add(1)
            with op_time.ns_timer():
                yield self._jit(batch, self._payload_caps(batch))

    def node_description(self):
        kind = "PosExplode" if self.position else "Explode"
        return (f"GenerateExec[{kind}{'Outer' if self.outer else ''}"
                f"({self.generator!r})]")
