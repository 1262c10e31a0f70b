"""Exchange execs — planner-produced repartitioning over the device mesh
(reference GpuShuffleExchangeExecBase.scala:167 planning entry,
prepareBatchShuffleDependency:277 device-side split, and the shuffle-plugin
UCX transport; SURVEY §2.5).

TPU-first redesign: no shuffle service, no serialized blocks. An exchange
is ONE compiled SPMD program over the mesh — evaluate the partition key
expressions on device, hash-partition rows (Spark-exact murmur3 pmod),
`lax.all_to_all` over the ICI axis, compact the received rows. XLA lowers
the collective to ICI neighbor exchanges with no host involvement.

Receive-buffer sizing (review finding r1: the worst-case default was
n_parts × capacity): a histogram program measures the actual max partition
load and max string byte length across all devices first — ONE host sync
per exchange, amortized over the whole stage — so the slot capacity fits
the data and fixed-width string lanes can never truncate.
"""

from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..columnar.batch import ColumnarBatch, committed_device, empty_batch
from ..columnar.column import (MIN_BUCKET, Column, StringColumn,
                               StructColumn, bucket_capacity)
from ..expr.core import Expression
from ..ops.basic import active_mask
from ..ops.strings import _placed, string_lengths
from ..parallel.exchange import (exchange_columns, negotiate_slot_cap,
                                 partition_ids)
from ..parallel.mesh import DATA_AXIS, active_mesh, mesh_axis_size
from ..types import Schema
from ..obs import events as obs_events
from ..obs import phase as obs_phase
from ..obs import op_span
from ..obs.dispatch import host_read, instrument
from .base import (BROADCAST_TIME, DEBUG, DISPATCH_METRICS, ESSENTIAL,
                   GATHER_METRICS,
                   GATHER_TIME, MODERATE,
                   NUM_GATHERS, NUM_INPUT_BATCHES, NUM_INPUT_ROWS,
                   NUM_OUTPUT_BATCHES,
                   NUM_OUTPUT_ROWS, NUM_UPLOADS, OP_TIME, PARTITION_SIZE,
                   PIPELINE_STAGE_METRICS, SHUFFLE_PACK_TIME,
                   SHUFFLE_READ_TIME, SHUFFLE_WRITE_TIME,
                   UPLOAD_METRICS, UPLOAD_PACK_TIME, TpuExec)
from .basic import InMemoryScanExec, bind_projection
from .coalesce import concat_batches


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _expand0(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def _stack_piece(batch):
    """A device's batch as its piece of the mesh program's input (a leading
    axis of 1), on the chip the batch sits on."""
    return _expand0(batch)


def _head(col, cap: int):
    """A column's first `cap` rows (the exchange compacts its rows to the
    front): its lanes sliced, a string column's bytes left as they are."""
    if isinstance(col, StringColumn):
        return StringColumn(col.data, col.offsets[:cap + 1],
                            col.validity[:cap], col.dtype)
    if isinstance(col, StructColumn):
        return type(col)(tuple(_head(k, cap) for k in col.children),
                         col.validity[:cap], col.dtype)
    return Column(col.data[:cap], col.validity[:cap], col.dtype)


def _rows_at(col, start, rows, cap: int):
    """`cap` rows of a column from traced row `start` on, the first `rows`
    of them live: lanes sliced at the offset (the caller keeps
    `start + cap` inside the column, so no slice is clamped); a string
    column's live bytes one block move to 0 in its source's byte bucket,
    zeros past them, its offsets shifted by as much and constant past the
    live rows."""
    live = jnp.arange(cap, dtype=jnp.int32) < rows
    valid = jax.lax.dynamic_slice_in_dim(col.validity, start, cap) & live
    if isinstance(col, StringColumn):
        offs = jax.lax.dynamic_slice_in_dim(col.offsets, start, cap + 1)
        base, end = offs[0], offs[rows]
        offs = jnp.where(jnp.arange(cap + 1, dtype=jnp.int32) <= rows,
                         offs, end) - base
        size = col.byte_capacity
        pos = jnp.arange(size, dtype=jnp.int32)
        data = jnp.where(pos < end - base, _placed(col.data, -base, size),
                         jnp.uint8(0))
        return StringColumn(data, offs, valid, col.dtype)
    if isinstance(col, StructColumn):
        return type(col)(tuple(_rows_at(k, start, rows, cap)
                               for k in col.children), valid, col.dtype)
    return Column(jax.lax.dynamic_slice_in_dim(col.data, start, cap), valid,
                  col.dtype)


def _unstack_shard(shard, cap: int):
    """A device's shard of the mesh program's output as a batch of its own,
    on that device, in the bucket of `cap` rows (every partition's rows
    fit: the caller sizes it to the largest)."""
    local = _squeeze0(shard)
    return ColumnarBatch([_head(c, cap) for c in local.columns],
                         local.num_rows, local.schema)


#: where the collective steps' output shards sat, process-cumulative:
#: device id -> start of that shard's slice of the leading (device) axis.
#: Uploads and `Column.from_numpy` place on device 0, so this is how a run
#: on real chips shows an exchange left it (chip_smoke.py --chips 4).
_PLACEMENT = {"exchanges": 0, "shards": {}}


def _note_exchange_placement(out) -> None:
    leaf = jax.tree_util.tree_leaves(out)[0]
    _PLACEMENT["exchanges"] += 1
    _PLACEMENT["shards"].update(
        (s.device.id, s.index[0].start or 0)
        for s in leaf.addressable_shards)


def exchange_placement(reset: bool = False) -> dict:
    out = {"exchanges": _PLACEMENT["exchanges"],
           "shards": dict(_PLACEMENT["shards"])}
    if reset:
        _PLACEMENT["shards"].clear()
    return out


def _host_key_array(col, n: int, idx=None):
    """Vectorized host materialization of a range-partition sort key
    (ISSUE 9 satellite): fixed-width columns become an object array via
    one astype (floats widened to f64 first, so NaN checks keep seeing
    python floats), strings decode from one contiguous bytes snapshot.
    Returns None for nested types (the caller falls back to to_pylist).
    `idx` restricts to sampled rows."""
    import numpy as np

    from ..columnar.column import Column, StringColumn
    from ..types import BinaryType
    if type(col) is Column:
        data, valid = host_read("exchange.keys", (col.data, col.validity))
        data, valid = np.asarray(data)[:n], np.asarray(valid)[:n]
        if idx is not None:
            data, valid = data[idx], valid[idx]
        if data.dtype.kind == "f":
            data = data.astype(np.float64)
        out = data.astype(object)  # python scalars, like .item()
        out[~valid] = None
        return out
    if isinstance(col, StringColumn):
        offsets, valid, buf = host_read(
            "exchange.keys", (col.offsets, col.validity, col.data))
        buf = np.asarray(buf).tobytes()
        binary = isinstance(col.dtype, BinaryType)
        rows = range(n) if idx is None else idx
        out = np.empty(n if idx is None else len(idx), dtype=object)
        for j, i in enumerate(rows):
            if valid[i]:
                raw = buf[offsets[i]: offsets[i + 1]]
                out[j] = raw if binary else raw.decode("utf-8")
        return out
    return None


#: what the mesh exchanges moved, process-cumulative (`counters()`): rounds
#: run; rows in; rows whose partition is not the chip they entered the
#: all-to-all from, and the bytes of their lanes in the all-to-all; summed
#: over exchange executions, each one's largest partition in rows and its
#: partition count; what consumers that merge partitions moved to one chip
#: (`onto_one_chip`): batches, their row slots and their bytes; and the
#: rounds dealt out by rows before the all-to-all (`spread_rounds`), the
#: rows they put on a chip other than their source and their lane bytes
_COUNTERS = {"rounds": 0, "rows_in": 0, "rows_off_chip": 0,
             "bytes_off_chip": 0, "max_partition_rows": 0, "partitions": 0,
             "batches_moved": 0, "rows_moved": 0, "bytes_moved": 0,
             "spread_rounds": 0, "rows_spread": 0, "bytes_spread": 0}


def counters() -> dict:
    return dict(_COUNTERS)


def reset_counters() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def _lane_bytes_per_row(batch: ColumnarBatch, width: int) -> int:
    """Bytes one row's slot carries through the all-to-all: each column's
    data (a string as its fixed-width padding and its length) and validity,
    and the slot's occupancy flag."""
    import numpy as np
    total = 1
    for c in batch.columns:
        if isinstance(c, StringColumn):
            total += width + 4 + 1
            continue
        for leaf in jax.tree_util.tree_leaves(c):
            total += leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
    return total


def partition_streams(node: TpuExec) -> Iterator[Iterator[ColumnarBatch]]:
    """`node`'s output as one batch stream per partition where its
    partitions sit on chips of their own, so that a consumer that merges
    batches merges those of one chip; else its one stream."""
    if node.partitions_on_chips:
        yield from node.execute_partitions()
    else:
        yield node.execute()


def onto_one_chip(batches: Iterable[ColumnarBatch]
                  ) -> Iterator[ColumnarBatch]:
    """The batches on the chip of the first one that sits on a single
    chip, for a consumer that merges partitions on chips of their own in
    one program (the top-N's last selection, a `complete` group-by over
    partitions): no program takes arrays of two chips, so each batch that
    sits elsewhere is moved there and counted (`batches_moved`, its
    capacity in `rows_moved`, `bytes_moved`). Batches on no single chip
    pass as they are, and so does everything outside a mesh session."""
    mesh = active_mesh()
    if mesh is None or mesh.size < 2:
        yield from batches
        return
    to = None
    for b in batches:
        at = committed_device(b)
        if to is None:
            to = at
        elif at is not None and at != to:
            _COUNTERS["batches_moved"] += 1
            _COUNTERS["rows_moved"] += b.capacity
            _COUNTERS["bytes_moved"] += sum(
                x.nbytes for x in jax.tree_util.tree_leaves(b))
            b = ColumnarBatch(*jax.device_put((b.columns, b.num_rows), to),
                              b.schema, b._host_rows)
        yield b


class ShuffleExchangeExec(TpuExec):
    """Hash-repartition child output across the mesh so rows with equal
    partition-key values colocate on one device shard.

    With no active mesh (or a 1-device mesh) the exchange is the identity —
    the single-partition plan needs no data movement. Otherwise each round
    runs ONE measure program and ONE all-to-all step over the mesh (a round
    whose input sits on one chip is first dealt out by rows over the
    chips, `_round_inputs`), and
    partition i comes out as arrays on mesh device i alone (its shards of
    the step's output), so every program downstream runs on that one chip.
    The flat stream yields each partition's staged PIECES in partition
    order (round 5: one piece at a time, a skewed shard is never
    concatenated whole); consumers that merge batches take partition
    boundaries from execute_partitions() (`partition_streams`)."""

    def __init__(self, partition_exprs: Sequence[Expression], child: TpuExec,
                 mesh=None):
        super().__init__(child)
        self.partition_exprs = list(partition_exprs)
        self._mesh = mesh if mesh is not None else active_mesh()
        self._bound = bind_projection(self.partition_exprs,
                                      child.output_schema)
        self._steps = {}
        # program sites, built last: the plan fingerprint they key on sees
        # the final fields. The per-device pieces in and out of the mesh
        # programs are programs of their own, run on the piece's chip.
        self._jit_stack = self._site(_stack_piece,
                                     label="ShuffleExchangeExec.stack")
        self._jit_unstack = self._site(_unstack_shard,
                                       label="ShuffleExchangeExec.unstack",
                                       static_argnums=(1,))
        self._jit_measure = self._jit_spread = None
        if self.n_partitions > 1:
            from ..parallel.mesh import shard_map_compat
            self._jit_measure = self._site(shard_map_compat(
                self._measure_kernel, mesh=self._mesh,
                in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS)),
                label="ShuffleExchangeExec.measure")
            self._jit_spread = self._site(self._spread_kernel,
                                          label="ShuffleExchangeExec.spread")

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def additional_metrics(self):
        return ((NUM_INPUT_BATCHES, DEBUG), (NUM_INPUT_ROWS, DEBUG),
                (PARTITION_SIZE, ESSENTIAL)) + PIPELINE_STAGE_METRICS \
            + DISPATCH_METRICS

    def _fingerprint_extras(self):
        # what the traced programs depend on beyond the child subtree: the
        # bound keys, the partition count and the mesh the collectives
        # run over (its axis and its devices, in order)
        mesh = self._mesh
        devices = () if mesh is None else \
            (mesh.axis_names, tuple(d.id for d in mesh.devices.flat))
        return ("mesh_exchange", self.n_partitions,
                tuple(b.semantic_key() for b in self._bound), devices)

    @property
    def runs_own_pipeline_stage(self) -> bool:
        # _drain_partition prefetches staged shard pieces through its
        # own pipelined() stage — a consumer must not stack another
        return True

    @property
    def n_partitions(self) -> int:
        return 1 if self._mesh is None else mesh_axis_size(self._mesh)

    @property
    def partitions_on_chips(self) -> bool:
        return self.n_partitions > 1

    # -- kernels -----------------------------------------------------------
    def _local_pid(self, local: ColumnarBatch, n: int):
        keys = [e.columnar_eval(local) for e in self._bound]
        return partition_ids(keys, local.num_rows, local.capacity, n)

    def _measure_kernel(self, stacked):
        """Per device (inside shard_map, like the step: no program sees a
        mesh-sharded operand it must partition by itself): the rows this
        device sends to each partition and its longest string in bytes.
        One host read of both sizes the round's receive slots."""
        n = self.n_partitions
        local = _squeeze0(stacked)
        pid = self._local_pid(local, n)
        ones = jnp.where(pid < n, jnp.int32(1), jnp.int32(0))
        counts = jax.ops.segment_sum(ones, pid.astype(jnp.int32),
                                     num_segments=n + 1)[:n]
        max_len = jnp.int32(0)
        act = active_mask(local.num_rows, local.capacity)
        for c in local.columns:
            if isinstance(c, StringColumn):
                lens = string_lengths(c)
                max_len = jnp.maximum(max_len,
                                      jnp.max(jnp.where(act, lens, 0)))
        return counts[None], max_len[None]

    def _spread_kernel(self, batch: ColumnarBatch):
        """On the chip the batch sits on: its n pieces of `capacity // n`
        rows, each with the leading axis of a piece of the mesh programs'
        input. Active rows are a prefix: with q, r = divmod(rows, n),
        piece d holds q + (d < r) rows from d * q + min(d, r) on, so the
        pieces differ by at most one row and no count is read to the host.
        With n dividing the capacity, every slice lies inside the batch."""
        n = self.n_partitions
        assert batch.capacity % n == 0, (batch.capacity, n)
        cap = batch.capacity // n
        q, r = batch.num_rows // n, batch.num_rows % n
        pieces = []
        for d in range(n):
            rows = q + (d < r).astype(q.dtype)
            start = d * q + jnp.minimum(d, r)
            cols = [_rows_at(c, start, rows, cap) for c in batch.columns]
            pieces.append(_expand0(ColumnarBatch(cols, rows, batch.schema)))
        return pieces

    def _get_step(self, cap: int, slot_cap: int, width: int):
        key = (cap, slot_cap, width)
        step = self._steps.get(key)
        if step is not None:
            return step
        n = self.n_partitions
        schema = self.output_schema

        def _exchange_step(stacked):
            local = _squeeze0(stacked)
            pid = self._local_pid(local, n)
            cols, n_recv = exchange_columns(
                list(local.columns), (), local.num_rows, local.capacity,
                DATA_AXIS, n, slot_cap=slot_cap, string_width=width,
                pid=pid)
            return _expand0(ColumnarBatch(cols, n_recv, schema))

        from ..parallel.mesh import shard_map_compat
        step = self._site(shard_map_compat(
            _exchange_step, mesh=self._mesh, in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS)),
            "ShuffleExchangeExec.exchange_step", key_salt=key)
        self._steps[key] = step
        return step

    def _round_inputs(self, batches: List[ColumnarBatch]):
        """The round's input as one piece a device of the mesh, each with
        a leading axis of 1 on the chip it sits on, and their row bucket;
        with the mesh index of the chip a spread round's rows sat on (-1
        for a chip outside the mesh), None where the round was not spread.

        Batches on several chips of the mesh are grouped by the chip they
        sit on (a partitioned producer's output stays where it is).
        Batches on one chip, or on none (ingest lands on one), are
        concatenated there and dealt out BY ROWS in one program (`spread`)
        where each chip's piece holds at least a smallest bucket; below
        that floor the one batch is device 0's piece. Batches on several
        chips not all of the mesh are dealt out round-robin. A device
        without a batch gets an empty one, made on that device."""
        from ..parallel.distributed import align_batches
        n = self.n_partitions
        devices = list(self._mesh.devices.flat)
        schema = self.output_schema
        where = [committed_device(b) for b in batches]
        if len({w for w in where if w is not None}) <= 1:
            one = batches[0] if len(batches) == 1 \
                else concat_batches(batches, schema)
            if one.capacity % n == 0 and one.capacity // n >= MIN_BUCKET:
                at = next(iter(jax.tree_util.tree_leaves(one.columns)[0]
                               .devices()))
                source = devices.index(at) if at in devices else -1
                return self._jit_spread(one), one.capacity // n, source
            groups = [[one]] + [[] for _ in range(n - 1)]
        elif all(w in devices for w in where):
            groups = [[b for b, w in zip(batches, where) if w == d]
                      for d in devices]
        else:
            groups = [batches[d::n] for d in range(n)]
        per_dev = []
        for d, g in enumerate(groups):
            if not g:
                per_dev.append(jax.device_put(empty_batch(schema),
                                              devices[d]))
            elif len(g) == 1:
                per_dev.append(g[0])
            else:
                per_dev.append(concat_batches(g, schema))
        per_dev = align_batches(per_dev)
        return ([self._jit_stack(b) for b in per_dev], per_dev[0].capacity,
                None)

    def _exchange_round(self, batches: List[ColumnarBatch]):
        """One SPMD exchange over a bounded group of input batches;
        returns the n received partitions, partition i on mesh device i
        alone, and the round's rows per partition."""
        import numpy as np
        from ..parallel.distributed import device_shards, place_on_mesh
        n = self.n_partitions
        schema = self.output_schema
        with op_span("exchange.mesh_round", phase="ici-collective"):
            pieces, cap, source = self._round_inputs(batches)
            stacked = place_on_mesh(pieces, self._mesh)
            counts, max_len = host_read("exchange.round",
                                        self._jit_measure(stacked))
            # one host sync per ROUND: size the receive buffer to the
            # measured max partition load, and string lanes to the
            # measured max byte length (truncation structurally
            # impossible)
            counts = np.asarray(counts)
            slot_cap = negotiate_slot_cap(int(counts.max()), cap)
            width = max(8, (int(np.max(max_len)) + 7) // 8 * 8)
            out = self._get_step(cap, slot_cap, width)(stacked)
            _note_exchange_placement(out)
            totals = counts.sum(axis=0)
            # every row of partition i lands on device i (the slots hold
            # the largest load), so the host knows each partition's rows,
            # and the partitions leave the receive grid (n x slot_cap
            # rows) in the bucket of the largest
            part_cap = min(bucket_capacity(max(int(totals.max()), 1)),
                           n * slot_cap)
            shards = [ColumnarBatch(s.columns, s.num_rows, schema,
                                    int(totals[i]))
                      for i, s in enumerate(
                          self._jit_unstack(t, part_cap)
                          for t in device_shards(out, self._mesh))]
        # counts[d] are the rows that entered the all-to-all from chip d
        rows = int(totals.sum())
        row_bytes = _lane_bytes_per_row(batches[0], width)
        off_chip = rows - int(np.trace(counts))
        _COUNTERS["rounds"] += 1
        _COUNTERS["rows_in"] += rows
        _COUNTERS["rows_off_chip"] += off_chip
        _COUNTERS["bytes_off_chip"] += off_chip * row_bytes
        if source is not None:
            spread = rows - (int(counts[source].sum()) if source >= 0 else 0)
            _COUNTERS["spread_rounds"] += 1
            _COUNTERS["rows_spread"] += spread
            _COUNTERS["bytes_spread"] += spread * row_bytes
        return shards, totals

    # -- drive -------------------------------------------------------------
    def internal_execute(self) -> Iterator[ColumnarBatch]:
        """Flat drive: staged shard pieces stream out one at a time in
        partition order (round 5, ADVICE r3 #2 resolved for real: a
        skewed shard is no longer concatenated whole at yield — peak
        device memory is one round of input + one staged PIECE).
        Consumers that need partition boundaries (ShuffledHashJoinExec,
        PartitionWiseSortExec) use execute_partitions() instead."""
        for gen in self.execute_partitions():
            yield from gen

    def _stream_single(self) -> Iterator[ColumnarBatch]:
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        for b in self.child.execute():
            in_batches.add(1)
            if b._host_rows is not None:
                in_rows.add(b._host_rows)
            else:
                in_rows.add_device(b.num_rows)
            yield b

    def execute_partitions(self) -> Iterator[Iterator[ColumnarBatch]]:
        """One lazy batch-generator per partition, in partition order.
        Each generator unspills its staged pieces one at a time."""
        if self.n_partitions == 1:
            yield self._stream_single()
            return
        staged = self._run_rounds()
        schema = self.output_schema
        for d in range(self.n_partitions):
            yield self._drain_partition(staged[d], schema)

    def _drain_partition(self, pieces, schema) -> Iterator[ColumnarBatch]:
        from ..columnar.batch import empty_batch as _eb
        out_rows = self.metrics[NUM_OUTPUT_ROWS]
        out_batches = self.metrics[NUM_OUTPUT_BATCHES]
        if not pieces:
            out_batches.add(1)
            yield _eb(schema)
            return

        def unspill() -> Iterator[ColumnarBatch]:
            it = iter(pieces)
            try:
                for sp in it:
                    try:
                        b = sp.get_batch()
                        sp.release()
                    except BaseException:
                        # a failed promotion (e.g. TpuRetryOOM escaping
                        # the retry loop) must still drop THIS piece's
                        # catalog entry, not just the unreached tail
                        sp.close()
                        raise
                    sp.close()
                    yield b
            finally:
                for sp in it:  # early close: drop the staged remainder
                    sp.close()

        # pipelined shuffle read (ISSUE 3): the unspill/host->device
        # promotion of piece k+1 overlaps the consumer's compute on k
        stage = self.pipeline_stage(unspill(), "exchange-read")
        try:
            for b in stage:
                out_batches.add(1)
                if b._host_rows is not None:
                    out_rows.add(b._host_rows)
                else:
                    out_rows.add_device(b.num_rows)
                yield b
        finally:
            stage.close()

    def _run_rounds(self):
        """Streamed, bounded rounds (round-2 verdict item 6): child
        batches flow through the ICI exchange in fixed-byte rounds; each
        round's received shards stage as SPILLABLE batches. Returns the
        per-partition staged piece lists."""
        from ..config import EXCHANGE_ROUND_BYTES, active_conf
        from ..memory.spillable import SpillableBatch

        n = self.n_partitions
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        round_budget = active_conf().get(EXCHANGE_ROUND_BYTES)
        staged: List[List[SpillableBatch]] = [[] for _ in range(n)]
        pending: List[ColumnarBatch] = []
        pending_bytes = 0
        self.rounds = 0
        self._part_totals = None
        # runtime statistics (ISSUE 11): the mesh exchange measures
        # exact per-partition ROW counts per round (its histogram
        # program) — bytes stay on device, so its skew basis is rows
        from ..obs import stats as obs_stats
        stats_rec = obs_stats.ExchangeRecorder(type(self).__name__,
                                               self._op_id, n)

        def flush():
            nonlocal pending, pending_bytes
            if not pending:
                return
            with self.metrics[OP_TIME].ns_timer():
                shards, totals = self._exchange_round(pending)
            # exact per-partition totals accumulate ACROSS rounds; the
            # metric is the max over partitions of the whole-stage totals
            self._part_totals = totals if self._part_totals is None \
                else self._part_totals + totals
            stats_rec.record_map(totals.tolist(), None, 0)
            for d, shard in enumerate(shards):
                staged[d].append(SpillableBatch.from_batch(shard))
            pending = []
            pending_bytes = 0
            self.rounds += 1

        for b in self.child.execute():
            in_batches.add(1)
            if b._host_rows is not None:
                in_rows.add(b._host_rows)
            else:
                in_rows.add_device(b.num_rows)
            pending.append(b)
            pending_bytes += b.device_size_bytes()
            if pending_bytes >= round_budget:
                flush()
        flush()
        if self._part_totals is not None:
            max_part = int(self._part_totals.max())
            _COUNTERS["max_partition_rows"] += max_part
            _COUNTERS["partitions"] += n
            self.metrics[PARTITION_SIZE].add(max_part)
            obs_events.emit("exchange", exec="ShuffleExchangeExec",
                            op_id=self._op_id, partitions=self.n_partitions,
                            rounds=self.rounds, max_partition_bytes=max_part)
            stats_rec.finish_and_emit()
        return staged

    def node_description(self):
        return (f"ShuffleExchangeExec[n={self.n_partitions}, "
                f"keys={self.partition_exprs!r}]")


class HostShuffleExchangeExec(TpuExec):
    """Hash-repartition through the host shuffle manager (the reference's
    MULTITHREADED shuffle mode, RapidsShuffleInternalManagerBase.scala:238/
    :569): partition ids are computed on device (Spark-exact murmur3 pmod),
    rows are gathered into compact host blocks, serialized + LZ4-compressed
    on the writer thread pool into per-map data+index files, then read back
    partition by partition on the reader pool.

    This is the always-works exchange: it needs no mesh, bounds device
    memory by partition (the out-of-core repartition the reference gets
    from Spark's file shuffle), and survives any partition count. The
    flat stream yields each partition's decoded blocks in partition
    order WITHOUT concatenation (round 5); partition-aware consumers
    take boundaries from execute_partitions()."""

    def __init__(self, partition_exprs: Sequence[Expression], child: TpuExec,
                 n_partitions: int, conf=None, partitioning: str = "hash",
                 range_order=None):
        """partitioning ∈ hash | roundrobin | single | range (the
        reference's GpuHashPartitioningBase / GpuRoundRobinPartitioning /
        GpuSinglePartitioning / GpuRangePartitioner). Range mode takes
        `range_order` = (ordinal, ascending, nulls_first) on the child
        schema and samples the data for split bounds like
        GpuRangePartitioner's reservoir sampling."""
        super().__init__(child)
        from ..config import SHUFFLE_DEVICE_PARTITION, active_conf
        self.partition_exprs = list(partition_exprs or [])
        self.n_partitions = int(n_partitions)
        self.partitioning = partitioning
        self.range_order = range_order
        self._conf = conf or active_conf()
        if partitioning == "hash":
            assert self.partition_exprs, "hash partitioning needs keys"
            self._bound = bind_projection(self.partition_exprs,
                                          child.output_schema)
            self._jit_pid = instrument(
                self._pid_kernel,
                label="HostShuffleExchangeExec.pid", owner=self)
        self._rr_offset = 0
        # device partition split (ISSUE 9): hash/roundrobin/single pids
        # are device-computable, so the split runs as ONE compiled
        # program (pid -> counts + stable permutation -> packed reorder
        # through the gather engine) + ONE packed D2H; range keeps the
        # host lane — its sampled split bounds are host objects
        self._device_partition = (
            partitioning in ("hash", "roundrobin", "single")
            and bool(self._conf.get(SHUFFLE_DEVICE_PARTITION)))
        # fused split+pack (ISSUE 10 satellite, the round-9 TODO): the
        # D2H packer is traced INTO the partition-split program, so a
        # written batch costs ONE dispatch (pid -> counts + permutation
        # -> packed reorder -> packed uint8 buffer) + ONE D2H copy,
        # instead of a split dispatch followed by a pack dispatch
        from ..columnar import transfer as _transfer
        self._jit_split = instrument(
            lambda b, off: _transfer.pack_split(
                *self._split_kernel(b, off)),
            label="HostShuffleExchangeExec.split_pack", owner=self)
        # ICI device-resident lane (ISSUE 16): when the active mesh's
        # axis size equals this exchange's partition count, map output
        # is exchanged device-to-device (jax.lax.all_to_all) instead of
        # being serialized through the host shuffle files; the host
        # lane below stays the fallback tier (range mode, mismatched
        # partition counts, open breaker, failed collective round)
        from ..config import SHUFFLE_ICI_ENABLED
        self._ici_enabled = bool(self._conf.get(SHUFFLE_ICI_ENABLED))
        self._ici_mesh = None
        # adaptive skew shield (ISSUE 19): set by a downstream
        # partition-aware probe consumer (ShuffledHashJoinExec) on its
        # STREAM-side exchange — a skew split needs map-output-granular
        # host files, so an armed splitter keeps this execution off the
        # ICI all-to-all (uneven splits don't fit the static device
        # collective); measured write bytes surface for the
        # single-build conversion consult
        self._adaptive_probe_split = False
        self._adaptive_write_bytes: Optional[int] = None
        self._ici_measure = None
        self._ici_steps = {}
        #: running per-round high-water marks (ISSUE 11 statistics as
        #: the slot-cap negotiation hint): flooring later rounds by the
        #: earlier measured load keeps the compiled step shape stable
        self._ici_cap_hint = 0
        self._ici_width_hint = 8
        #: host unpack templates per compiled shape key (abstract shapes
        #: via eval_shape — no device work, no gather-recorder side
        #: effects: eval_shape runs OUTSIDE the tracker's observe)
        self._split_templates = {}
        from ..ops.gather import GatherTracker
        self._gather_track = GatherTracker(self.metrics[NUM_GATHERS],
                                           self.metrics[GATHER_TIME])

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def additional_metrics(self):
        return ((NUM_INPUT_BATCHES, DEBUG), (NUM_INPUT_ROWS, DEBUG),
                (PARTITION_SIZE, ESSENTIAL), SHUFFLE_WRITE_TIME,
                SHUFFLE_READ_TIME, (SHUFFLE_PACK_TIME, MODERATE)) \
            + GATHER_METRICS + UPLOAD_METRICS + PIPELINE_STAGE_METRICS \
            + DISPATCH_METRICS

    @property
    def runs_own_pipeline_stage(self) -> bool:
        # _read_partition prefetches fetch + LZ4 decode through its own
        # pipelined() stage — a consumer must not stack another
        return True

    def _fingerprint_extras(self):
        # everything this exec's traced programs depend on beyond the
        # child subtree: the partitioning mode and count, the bound key
        # expressions, the range ordering and the two lane gates
        # (ISSUE 16: the ICI exchange step is a _site program — equal
        # fingerprints let a later identical plan reuse it compiled)
        return ("host_shuffle", self.partitioning, self.n_partitions,
                tuple(repr(e) for e in self.partition_exprs),
                self.range_order, self._device_partition,
                self._ici_enabled)

    def _pid_kernel(self, batch: ColumnarBatch):
        keys = [e.columnar_eval(batch) for e in self._bound]
        return partition_ids(keys, batch.num_rows, batch.capacity,
                             self.n_partitions)

    # -- device partition split (ISSUE 9) ----------------------------------
    def _split_kernel(self, batch: ColumnarBatch, rr_offset):
        """One traced program: pid -> per-partition counts + pid-stable
        permutation -> partition-major reorder through the gather engine
        (ops/partition_split.py). rr_offset is only read on the
        roundrobin lane (hash pids come from the key expressions)."""
        from ..ops.partition_split import partition_table, reorder_columns
        n = self.n_partitions
        if self.partitioning == "hash":
            pid = self._pid_kernel(batch)
        else:  # roundrobin
            iota = jnp.arange(batch.capacity, dtype=jnp.int32)
            pid = (iota + rr_offset) % jnp.int32(n)
            pid = jnp.where(active_mask(batch.num_rows, batch.capacity),
                            pid, jnp.int32(n))
        counts, order = partition_table(pid, batch.num_rows,
                                        batch.capacity, n)
        return counts, reorder_columns(batch.columns, order,
                                       batch.num_rows)

    def _device_split(self, b: ColumnarBatch, n: int):
        """Split one batch on device: returns (host columns in
        partition-major order, exclusive bounds (n_partitions+1,)).
        The split, the reorder AND the D2H packer run as ONE fused
        traced program (ISSUE 10 satellite) whose packed uint8 buffer
        lands the count table and the reordered payload in ONE D2H copy
        — the offset table is the split's only host-synced control
        value, and a written batch costs exactly one dispatch."""
        import numpy as np
        from ..columnar import transfer
        if self.partitioning == "single":
            # no permutation needed: the batch IS partition 0's slice
            cols, _n = transfer.fetch_batch_host(b)
            counts = np.zeros(self.n_partitions, np.int64)
            counts[0] = n
        else:
            off = self._rr_offset
            if self.partitioning == "roundrobin":
                self._rr_offset = int((self._rr_offset + n)
                                      % self.n_partitions)
            # observe keyed by the compiled program shape so the
            # trace-time gather counts replay exactly on jit cache hits
            key = (self.partitioning, b.capacity, tuple(
                (tuple(leaf.shape), str(leaf.dtype))
                for leaf in jax.tree_util.tree_leaves(list(b.columns))))
            tmpl = self._split_templates.get(key)
            if tmpl is None:
                # abstract column shapes for the host-side unpack of the
                # fused program's packed buffer (computed BEFORE observe:
                # eval_shape re-traces the split and must not double the
                # tracker's structural gather counts)
                _c, tmpl = jax.eval_shape(self._split_kernel, b,
                                          jnp.int32(off))
                self._split_templates[key] = tmpl
            with self._gather_track.observe(key):
                buf_dev = self._jit_split(b, jnp.int32(off))
            buf = host_read("exchange.split", buf_dev)  # the ONE d2h copy
            transfer.note_d2h(buf.nbytes)
            counts, cols = transfer.unpack_split_host(
                buf, tmpl, self.n_partitions)
        bounds = np.zeros(self.n_partitions + 1, np.int64)
        np.cumsum(counts, out=bounds[1:])
        return cols, bounds

    def _write_map(self, b: ColumnarBatch, n: int, range_bounds, handle,
                   mgr, map_id: int, register: bool = True):
        """Partition + serialize + write one map task's output, on the
        lane the conf selects. Returns (writer, lane, pack_ns,
        rows_per_partition) — the row counts feed the runtime-statistics
        plane (ISSUE 11) and come free from the work each lane already
        did (the split's count table / the host partition batches). Both
        the steady-state write loop and the partition-recovery recompute
        route through here, so recovered map outputs replay the exact
        lane (and round-robin offsets) of the original write."""
        import time as _time

        import numpy as np
        from ..shuffle.manager import (HostShuffleWriter,
                                       partition_batch_host)
        writer = HostShuffleWriter(handle, map_id, mgr, self._conf)
        if self._device_partition and not n:
            # empty batch: zero frames, no partitioning work at all
            writer.write([[] for _ in range(self.n_partitions)],
                         register=register, lane="device")
            return writer, "device", 0, [0] * self.n_partitions
        if self._device_partition:
            t0 = _time.perf_counter_ns()
            cols, bounds = self._device_split(b, n)
            pack_ns = _time.perf_counter_ns() - t0
            self.metrics[SHUFFLE_PACK_TIME].add(pack_ns)
            from ..shuffle.manager import note_shuffle_write
            note_shuffle_write(pack_ns=pack_ns)
            packed = ColumnarBatch(cols, n, self.output_schema)
            writer.write_slices(packed, bounds, register=register)
            rows_pp = np.diff(np.asarray(bounds)).tolist()
            return writer, "device", pack_ns, rows_pp
        pid = self._pid_for(b, n, range_bounds)
        parts = partition_batch_host(b, pid, self.n_partitions)
        writer.write([[p] if p.num_rows_host else [] for p in parts],
                     register=register)
        return writer, "host", 0, [p.num_rows_host for p in parts]

    # -- partition id per mode --------------------------------------------
    def _host_keys(self, batch: ColumnarBatch, n: int, stride: int = 1):
        """First-sort-key values as host objects (None for nulls). The
        numeric and string common cases vectorize off the column's host
        buffers (one astype(object) / one bytes slice pass) instead of
        the old element-by-element object-array build; nested types keep
        the to_pylist fallback. With a stride, only the sampled rows
        materialize (the bounds pass needs ~512 values, not a
        full-column to_pylist)."""
        import numpy as np
        ordinal, _asc, _nf = self.range_order
        col = batch.columns[ordinal]
        idx = np.arange(0, n, stride, dtype=np.int64) if stride > 1 \
            else None
        fast = _host_key_array(col, n, idx)
        if fast is not None:
            return fast
        # nested fallback (array/map/struct/decimal128 sort keys)
        if idx is not None:
            from ..shuffle.serializer import host_gather_column
            col = host_gather_column(col, idx)
            n = len(idx)
        vals = col.to_pylist(n)
        return np.array(vals, dtype=object)

    @staticmethod
    def _is_nan(k) -> bool:
        return isinstance(k, float) and k != k

    def _range_bounds(self, key_samples):
        """Sampled split bounds over the first sort key (reference
        GpuRangePartitioner: sample → sort → n-1 evenly spaced bounds).
        NaN keys are excluded (they route to the greatest partition like
        Spark's NaN-sorts-last); all-equal keys collapse to one
        partition, which is still exact."""
        sample = [k for k in key_samples
                  if k is not None and not self._is_nan(k)]
        sample.sort()
        if not sample:
            return []
        idx = [len(sample) * (i + 1) // self.n_partitions
               for i in range(self.n_partitions - 1)]
        return [sample[min(i, len(sample) - 1)] for i in idx]

    def _pid_for(self, batch: ColumnarBatch, n: int, bounds):
        import numpy as np
        mode = self.partitioning
        if mode == "hash":
            return np.asarray(host_read("exchange.pid",
                                        self._jit_pid(batch)))[:n]
        if mode == "single":
            return np.zeros(n, np.int64)
        if mode == "roundrobin":
            pid = (np.arange(n, dtype=np.int64) + self._rr_offset) \
                % self.n_partitions
            self._rr_offset = int((self._rr_offset + n)
                                  % self.n_partitions)
            return pid
        if mode == "range":
            keys = self._host_keys(batch, n)
            _ordinal, asc, nulls_first = self.range_order
            null_pid = 0 if nulls_first else self.n_partitions - 1
            null_mask = np.array([k is None for k in keys], np.bool_)
            # NaN sorts greatest (Spark float ordering): last partition
            # ascending, first descending — never through searchsorted
            nan_mask = np.array([self._is_nan(k) for k in keys], np.bool_)
            safe = np.array([bounds[0] if (k is None or self._is_nan(k))
                             else k for k in keys], dtype=object) \
                if bounds else keys
            if bounds:
                idx = np.searchsorted(np.array(bounds, dtype=object),
                                      safe, side="left").astype(np.int64)
            else:
                idx = np.zeros(n, np.int64)
            idx[nan_mask] = self.n_partitions - 1
            if not asc:
                idx = self.n_partitions - 1 - idx
            idx[null_mask] = null_pid
            return idx
        raise ValueError(f"unknown partitioning {mode!r}")

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        import numpy as np  # noqa: F401 — used by _pid_for

        for gen in self.execute_partitions(flat=True):
            yield from gen

    def execute_partitions(self, flat: bool = False,
                           ) -> "Iterator[Iterator[ColumnarBatch]]":
        """One lazy batch-generator per partition, in partition order:
        decoded blocks stream WITHOUT concatenation (ADVICE r3 #2 — a
        skewed partition's device peak is one decoded block; the old
        contract concatenated the whole shard at yield). Flat consumers
        get the same pieces via internal_execute; partition-aware ones
        (ShuffledHashJoinExec, PartitionWiseSortExec) take the
        boundaries from here.

        Lane selection (ISSUE 16): the ICI device-resident lane when
        eligible — conf on, active mesh axis == partition count,
        device-computable partitioning, breaker closed — else the host
        serialize/LZ4 lane. The ICI lane itself degrades to the host
        lane mid-stream on a failed collective round.

        `flat` marks a partition-oblivious consumer (internal_execute):
        only then may the adaptive replanner coalesce adjacent tiny
        partitions into one read — partition-AWARE consumers (shuffled
        joins, partition-wise sort) must see the static boundaries or
        a zipped pair of exchanges would desync."""
        self._adaptive_write_bytes = None
        if self._ici_eligible():
            yield from self._execute_partitions_ici()
            return
        yield from self._execute_partitions_host(flat=flat)

    def _execute_partitions_host(self, override_source=None,
                                 stats_rec=None, flat: bool = False
                                 ) -> "Iterator[Iterator[ColumnarBatch]]":
        """The host shuffle-manager lane (and the ICI lane's fallback
        tier). `override_source` replaces the child stream when the ICI
        lane degrades mid-stream: the leftover batches it already
        pulled plus the unconsumed remainder. On that path lineage
        capture is off (a recompute would replay the child from batch
        zero and rewrite the wrong map output), the round-robin cursor
        continues from where the ICI rounds left it, and `stats_rec`
        carries the ICI rounds' map records in — the write phase below
        appends its own and emits the execution's ONE exchange_stats
        record."""
        from ..shuffle.manager import HostShuffleReader, shuffle_manager
        mgr = shuffle_manager()
        handle = mgr.register(self.n_partitions, self.output_schema)
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        if override_source is None:
            self._rr_offset = 0
        state = {"done": 0, "outer_done": False, "closed": False}
        try:
            if override_source is not None:
                source = override_source
                bounds = None
            elif self.partitioning == "range":
                # bounds need a full pass: buffer the input as SPILLABLE
                # handles (sampling keys host-side as they stream by), so
                # the buffered data stays under the memory budget — the
                # point of the host-shuffled sort (reference
                # GpuRangePartitioner sampling + spillable buffering)
                from ..memory.spillable import SpillableBatch
                spillables = []
                key_samples: list = []
                for b in self.child.execute():
                    nb = b.num_rows_host
                    if nb:
                        key_samples.extend(self._host_keys(
                            b, nb, stride=max(1, nb // 512)))
                    spillables.append(SpillableBatch.from_batch(b))
                bounds = self._range_bounds(key_samples)

                def drain():
                    for sp in spillables:
                        batch = sp.get_batch()
                        try:
                            yield batch
                        finally:
                            sp.release()
                            sp.close()

                source = drain()
            else:
                source = self.child.execute()
                bounds = None
            from ..config import PARTITION_RECOVERY_ENABLED
            # lineage capture (ISSUE 6): range mode is excluded — its
            # partition bounds come from sampling a spillable buffer
            # that is consumed by the write pass, so a later recompute
            # could not replay the identical pid assignment
            capture_lineage = (
                self.partitioning != "range"
                and override_source is None
                and bool(self._conf.get(PARTITION_RECOVERY_ENABLED)))
            # runtime statistics (ISSUE 11): per-map-output and
            # per-partition row/byte distributions, recorded from the
            # counts the split/serializer already produced — into the
            # governed query's RuntimeStats (when one is running on
            # this thread) and the process-wide collector
            from ..obs import stats as obs_stats
            from ..obs import telemetry
            if stats_rec is None:
                stats_rec = obs_stats.ExchangeRecorder(
                    type(self).__name__, self._op_id, self.n_partitions)
            map_id = 0
            for b in source:
                in_batches.add(1)
                n = b.num_rows_host
                in_rows.add(n)
                # time only the shuffle work (partition/serialize/write),
                # not the upstream compute driving child.execute().
                # Phase attribution (ISSUE 17): the map write's wall is
                # host-pack/serialize except the writer's file-IO share,
                # which the nested add() carves out as shuffle-io (and
                # the span excludes from its own exclusive time)
                with self.metrics[SHUFFLE_WRITE_TIME].ns_timer(), \
                        op_span("exchange.map_write",
                                phase="host-pack-serialize"):
                    writer, lane, pack_ns, rows_pp = self._write_map(
                        b, n, bounds, handle, mgr, map_id)
                    obs_phase.add("shuffle-io", writer.io_ns)
                stats_rec.record_map(rows_pp, writer.partition_bytes,
                                     writer.bytes_written)
                telemetry.add("exchange.write_bytes",
                              writer.bytes_written)
                if capture_lineage:
                    handle.lineage[mgr.map_data_path(
                        handle.shuffle_id, map_id)] = \
                        self._make_recompute(handle, mgr, map_id)
                self.metrics[PARTITION_SIZE].add(writer.bytes_written)
                obs_events.emit("exchange",
                                exec="HostShuffleExchangeExec",
                                op_id=self._op_id, map_id=map_id,
                                partitions=self.n_partitions,
                                bytes=writer.bytes_written,
                                partitioning=self.partitioning)
                obs_events.emit("shuffle_write",
                                exec="HostShuffleExchangeExec",
                                op_id=self._op_id, map_id=map_id,
                                lane=lane, bytes=writer.bytes_written,
                                frames=writer.frames_written,
                                pack_ns=pack_ns,
                                serialize_ns=writer.serialize_ns,
                                io_ns=writer.io_ns)
                map_id += 1
            # one gather_stats record per execution, the wired-exec
            # convention (the write phase is where this exec's gathers
            # happen — emit once it is complete, not at stream close)
            self._gather_track.emit_event(type(self).__name__,
                                          self._op_id)
            # one exchange_stats record per execution: the skew/
            # distribution summary profile_report rolls up and the AQE
            # loop (ROADMAP 4) will consult
            stats_rec.finish_and_emit()
            #: measured write total for the single-build conversion
            #: consult (ISSUE 19) — host lane only (ICI rounds record
            #: rows, not bytes)
            self._adaptive_write_bytes = stats_rec.total_bytes() or None
            reader = HostShuffleReader(handle, mgr, self._conf)
            n = self.n_partitions
            # adaptive replanning (ISSUE 19): the consult point — the
            # write phase measured every partition exactly, no reader
            # stream exists yet. The ICI fallback drain is excluded
            # (its stats carry rows only, and lineage is off).
            split_plan, flat_groups = {}, None
            if override_source is None:
                split_plan, flat_groups = self._adaptive_read_plan(
                    stats_rec, reader, handle, flat)

            def cleanup_if_finished():
                if state["outer_done"] and state["done"] >= n \
                        and not state["closed"]:
                    state["closed"] = True
                    mgr.unregister(handle)

            out_rows = self.metrics[NUM_OUTPUT_ROWS]
            out_batches = self.metrics[NUM_OUTPUT_BATCHES]

            def part_stream(p, cell):
                # the handle must outlive the INNER streams: a consumer
                # may list() the outer generator before reading any
                # partition (exhausting the outer must not tear down the
                # shuffle files under the readers)
                groups = split_plan.get(p)
                inner = self._read_partition(reader, p) \
                    if groups is None \
                    else self._read_partition_split(reader, p, groups,
                                                    handle)
                try:
                    for b in inner:
                        out_batches.add(1)
                        if b._host_rows is not None:
                            out_rows.add(b._host_rows)
                        else:
                            out_rows.add_device(b.num_rows)
                        yield b
                finally:
                    # join the pipelined reader (inner's finally closes
                    # its stage) BEFORE _mark_done can unregister the
                    # shuffle files under a still-running producer
                    inner.close()
                    _mark_done(cell)

            def _mark_done(cell):
                if not cell[0]:
                    cell[0] = True
                    state["done"] += 1
                    cleanup_if_finished()

            def _mark_done_all(cells):
                for cell in cells:
                    _mark_done(cell)

            def group_stream(ps, cells):
                # a coalesced read (ISSUE 19 decision 3): chain the
                # member partitions' UNCHANGED streams — same stages,
                # same batches, same order — so the merge is pure read
                # grouping; the finally settles every member's cell
                try:
                    for p, cell in zip(ps, cells):
                        yield from part_stream(p, cell)
                finally:
                    _mark_done_all(cells)

            import weakref
            try:
                if flat_groups is None:
                    for p in range(n):
                        cell = [False]
                        g = part_stream(p, cell)
                        # a NEVER-STARTED generator runs no finally even
                        # on close: the weakref finalizer keeps an
                        # abandoned partition stream from leaking the
                        # shuffle handle
                        weakref.finalize(g, _mark_done, cell)
                        yield g
                else:
                    for ps in flat_groups:
                        cells = [[False] for _ in ps]
                        if len(ps) == 1:
                            g = part_stream(ps[0], cells[0])
                            weakref.finalize(g, _mark_done, cells[0])
                        else:
                            g = group_stream(ps, cells)
                            weakref.finalize(g, _mark_done_all, cells)
                        yield g
            finally:
                state["outer_done"] = True
                cleanup_if_finished()
        except BaseException:
            # write-phase failure or early abandonment of the outer
            # generator: tear down now (cleanup_if_finished guards the
            # registered state against a second unregister)
            if not state["closed"]:
                state["closed"] = True
                mgr.unregister(handle)
            raise

    # -- ICI device-resident lane (ISSUE 16) -------------------------------
    def _ici_eligible(self) -> bool:
        """May this execution take the device-to-device lane? Conf on,
        a device-computable partitioning (range bounds are host
        objects), an active mesh whose axis size IS the partition
        count (the all-to-all sends one slot grid row per peer), and a
        closed `ici_exchange` breaker. A no answer is the degradation
        decision: the host lane is always correct."""
        if not self._ici_enabled or self.n_partitions <= 1:
            return False
        if self.partitioning not in ("hash", "roundrobin", "single"):
            return False
        # variable-length nested payloads (array/map) have no packed
        # slot-grid representation yet — parallel/exchange.py exchanges
        # fixed-width lanes, strings and struct/decimal limbs only
        from ..types import ArrayType, MapType, StructType

        def _collective_ok(dt) -> bool:
            if isinstance(dt, (ArrayType, MapType)):
                return False
            if isinstance(dt, StructType):
                return all(_collective_ok(f.data_type)
                           for f in dt.fields)
            return True

        if not all(_collective_ok(f.data_type)
                   for f in self.output_schema.fields):
            return False
        mesh = active_mesh()
        if mesh is None or mesh_axis_size(mesh) != self.n_partitions:
            return False
        from . import lifecycle
        if not lifecycle.breaker_allows("ici_exchange"):
            return False
        # adaptive skew shield (ISSUE 19): an armed skew splitter needs
        # the host lane's map-output-granular files — uneven sub-reads
        # don't fit the static device collective. The stand-down is a
        # degradation decision, reported through the ISSUE 16 seam
        # (fallback event + counter) so the lane change is visible.
        if self._adaptive_probe_split:
            from ..config import ADAPTIVE_ENABLED, ADAPTIVE_SKEW_FACTOR
            if self._conf.get(ADAPTIVE_ENABLED) \
                    and self._conf.get(ADAPTIVE_SKEW_FACTOR) > 0:
                from ..shuffle.manager import note_ici_exchange
                note_ici_exchange(fallbacks=1)
                obs_events.emit("ici_exchange",
                                exec=type(self).__name__,
                                op_id=self._op_id, fallback=True,
                                reason="adaptive_skew_split")
                return False
        self._ici_mesh = mesh
        return True

    def _ici_pid(self, local: ColumnarBatch, rr_off, n: int):
        """Per-device partition ids inside the SPMD bodies. rr_off is
        the device's round-robin cursor at its batch's first row (a
        traced scalar input — the host tracks it across rounds so the
        assignment is bit-identical to the host lane's)."""
        if self.partitioning == "hash":
            return self._pid_kernel(local)
        act = active_mask(local.num_rows, local.capacity)
        if self.partitioning == "roundrobin":
            iota = jnp.arange(local.capacity, dtype=jnp.int32)
            pid = (iota + rr_off) % jnp.int32(n)
            return jnp.where(act, pid, jnp.int32(n))
        return jnp.where(act, jnp.int32(0), jnp.int32(n))  # single

    def _ici_measure_kernel(self, stacked, rr):
        """Per-device partition histogram + max string byte length,
        vmapped over the device axis (pure measurement, no collective):
        ONE host sync per round sizes the negotiated slot grid. The
        histogram comes back per device — one row per map batch — so
        the runtime-statistics recorder keeps the host lane's per-map
        granularity."""
        n = self.n_partitions

        def per_dev(local: ColumnarBatch, off):
            pid = self._ici_pid(local, off, n)
            ones = jnp.where(pid < n, jnp.int32(1), jnp.int32(0))
            counts = jax.ops.segment_sum(ones, pid.astype(jnp.int32),
                                         num_segments=n + 1)
            max_len = jnp.int32(0)
            act = active_mask(local.num_rows, local.capacity)
            for c in local.columns:
                if isinstance(c, StringColumn):
                    lens = string_lengths(c)
                    max_len = jnp.maximum(
                        max_len, jnp.max(jnp.where(act, lens, 0)))
            return jnp.max(counts[:n]), max_len, counts[:n]

        max_count, max_len, per_map = jax.vmap(per_dev)(stacked, rr)
        return jnp.max(max_count), jnp.max(max_len), per_map

    def _get_ici_measure(self):
        if self._ici_measure is None:
            self._ici_measure = self._site(
                self._ici_measure_kernel,
                "HostShuffleExchangeExec.ici_measure")
        return self._ici_measure

    def _get_ici_step(self, cap: int, slot_cap: int, width: int):
        """The exchange program per (capacity, slot_cap, string width)
        shape AND mesh identity: partition-split into the (n, slot_cap)
        send grid and all-to-all every column lane over the mesh axis —
        built through _site so an identical later plan reuses the
        compiled program (exec/stage_compiler.py fingerprint cache).
        The compiled step closes over the mesh it was built under, so
        the mesh's axis names + devices are part of the key (and the
        fingerprint salt): a session that installs a different mesh
        later — same axis size, different Mesh/device set — gets a
        fresh step instead of a collective over the stale mesh."""
        mesh = self._ici_mesh
        key = (cap, slot_cap, width, mesh.axis_names,
               tuple(mesh.devices.flat))
        step = self._ici_steps.get(key)
        if step is not None:
            return step
        n = self.n_partitions
        schema = self.output_schema

        def spmd(stacked, rr):
            local = _squeeze0(stacked)
            pid = self._ici_pid(local, rr[0], n)
            cols, n_recv = exchange_columns(
                list(local.columns), (), local.num_rows, local.capacity,
                DATA_AXIS, n, slot_cap=slot_cap, string_width=width,
                pid=pid)
            return _expand0(ColumnarBatch(cols, n_recv, schema))

        from ..parallel.mesh import shard_map_compat
        step = self._site(
            shard_map_compat(spmd, mesh=mesh,
                             in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                             out_specs=P(DATA_AXIS)),
            "HostShuffleExchangeExec.ici_exchange_step", key_salt=key)
        self._ici_steps[key] = step
        return step

    def _ici_exchange_round(self, batches, rr_offs, round_idx: int):
        """One collective round: exactly ONE map batch per device (in
        map order, padded with empties), so partition p's received rows
        concatenate across devices in the host lane's map order —
        byte-identical per-partition row order. Returns the n received
        shard batches + the (n_devices, n_partitions) per-map-batch row
        histogram (sum over axis 0 = the round's partition totals)."""
        import time as _time

        import numpy as _np
        from ..parallel.distributed import stack_batches, unstack_batches
        from ..shuffle.manager import note_ici_exchange
        n = self.n_partitions
        schema = self.output_schema
        per_dev = list(batches) + [empty_batch(schema)
                                   for _ in range(n - len(batches))]
        cap = max(b.capacity for b in per_dev)
        per_dev = [b.sized_to(cap) for b in per_dev]
        rr = jnp.asarray(list(rr_offs) + [0] * (n - len(rr_offs)),
                         dtype=jnp.int32)
        from . import lifecycle
        lifecycle.engage_domain("ici_exchange")
        t0 = _time.perf_counter_ns()
        # the collective dispatch is the chaos seam: the fault key is
        # the deterministic round ordinal, and dispatch metrics land on
        # this exec through the stage-boundary harness. Phase
        # attribution (ISSUE 17): the whole measured round — stack,
        # measure, all-to-all step, unstack — is ici-collective; the
        # span keeps its cached dispatches out of device-compute
        # a round's collective programs hang-bound (when
        # dispatch.timeoutMs > 0) against the ici_exchange breaker, so
        # a wedged all-to-all degrades to the host lane like any other
        # classified-transient round failure (ISSUE 20)
        from . import speculation_shield
        with op_span("exchange.ici_round", phase="ici-collective"), \
                speculation_shield.dispatch_domain("ici_exchange"), \
                self.batch_harness(fault_point="shuffle.ici_exchange",
                                   fault_key=f"r{round_idx}",
                                   metric_scope=True):
            stacked = stack_batches(per_dev)
            max_count, max_len, per_map = host_read(
                "exchange.ici_round", self._get_ici_measure()(stacked, rr))
            # one host sync per round; the running high-water hints
            # keep later (smaller) rounds on the SAME compiled step
            self._ici_cap_hint = max(self._ici_cap_hint, int(max_count))
            slot_cap = negotiate_slot_cap(int(max_count), cap,
                                          hint=self._ici_cap_hint)
            self._ici_width_hint = max(
                self._ici_width_hint, (int(max_len) + 7) // 8 * 8)
            width = self._ici_width_hint
            out = self._get_ici_step(cap, slot_cap, width)(stacked, rr)
            _note_exchange_placement(out)
            shards = unstack_batches(out, n)
        collective_ns = _time.perf_counter_ns() - t0
        per_map = _np.asarray(per_map)
        moved = sum(s.device_size_bytes() for s in shards)
        rows = int(per_map.sum())
        fill = rows / float(n * n * slot_cap) if slot_cap else 0.0
        self.metrics[SHUFFLE_PACK_TIME].add(collective_ns)
        note_ici_exchange(rounds=1, batches=len(batches), bytes=moved,
                          collective_ns=collective_ns)
        obs_events.emit("ici_exchange", exec="HostShuffleExchangeExec",
                        op_id=self._op_id, round=round_idx,
                        partitions=n, batches=len(batches), rows=rows,
                        bytes=moved, slot_cap=slot_cap, width=width,
                        fill=round(fill, 4),
                        collective_ns=collective_ns)
        return shards, per_map

    def _execute_partitions_ici(self):
        """Drive the device-resident lane: child batches group into
        one-batch-per-device rounds, each round runs the measured
        all-to-all program, received shards stage as SPILLABLE catalog
        entries tagged `ici_exchange` (the PR 4-6 spill/quota contracts
        hold). Zero host serialize frames, zero per-batch D2H/H2D.

        Degradation: a classified-transient failure of the COLLECTIVE
        ROUND itself (or an injected `shuffle.ici_exchange` fault)
        records against the `ici_exchange` breaker domain and the rest
        of the stream — the failed round's batches are still in hand —
        degrades to the host serialize lane; partitions then drain the
        staged ICI pieces FIRST and the host partitions after,
        preserving map order. The seam is deliberately THAT narrow: a
        transient raised while pulling from the CHILD stream must
        propagate to the task-retry layer exactly as the host lane
        would propagate it — a generator that raised is finalized, so
        chaining its remainder would silently drop every unconsumed
        child batch and return partial results."""
        from itertools import chain

        from .. import faults
        from ..memory.spillable import SpillableBatch
        from ..obs import stats as obs_stats
        from ..shuffle.manager import note_ici_exchange
        from . import lifecycle
        n = self.n_partitions
        schema = self.output_schema
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        self._rr_offset = 0
        self._ici_cap_hint = 0
        self._ici_width_hint = 8
        staged: List[List[SpillableBatch]] = [[] for _ in range(n)]
        pending: List[ColumnarBatch] = []
        pending_rows = 0
        rr_offs: List[int] = []
        part_totals = None
        round_idx = 0
        fell_back = False
        stats_rec = obs_stats.ExchangeRecorder(type(self).__name__,
                                               self._op_id, n)
        source = self.child.execute()
        try:
            def try_flush() -> bool:
                """Run one collective round over `pending`; True on
                success. Only the round dispatch is inside the
                degradation seam — once its shards are in hand they
                are staged unconditionally (replaying the same batches
                on the host lane after a partial stage would duplicate
                rows)."""
                nonlocal part_totals, pending_rows, round_idx, fell_back
                try:
                    with self.metrics[SHUFFLE_WRITE_TIME].ns_timer():
                        shards, per_map = self._ici_exchange_round(
                            pending, rr_offs, round_idx)
                except Exception as e:  # noqa: BLE001 — degradation seam
                    if not faults.is_task_transient(e):
                        raise
                    # degradation decision: count the failure against
                    # the breaker domain (enough of them opens the
                    # breaker and later exchanges skip the lane up
                    # front) and hand the batches still in hand + the
                    # unconsumed remainder to the always-works host lane
                    lifecycle.record_domain_failure("ici_exchange")
                    note_ici_exchange(fallbacks=1)
                    obs_events.emit("ici_exchange",
                                    exec="HostShuffleExchangeExec",
                                    op_id=self._op_id, round=round_idx,
                                    fallback=True, error=str(e)[:200])
                    # the failed round's batches replay on the host
                    # lane: rewind the round-robin cursor to the
                    # round's first batch so the host lane assigns the
                    # SAME partitions the collective would have
                    if rr_offs:
                        self._rr_offset = rr_offs[0]
                    fell_back = True
                    return False
                for d, shard in enumerate(shards):
                    staged[d].append(SpillableBatch.from_batch(
                        shard, origin="ici_exchange"))
                totals = per_map.sum(axis=0)
                part_totals = totals if part_totals is None \
                    else part_totals + totals
                # one stats record per MAP BATCH (the host lane's
                # granularity): the measure program's per-device
                # histogram rows, skipping the round's padding devices
                for d in range(len(pending)):
                    stats_rec.record_map(per_map[d].tolist(), None, 0)
                in_batches.add(len(pending))
                in_rows.add(pending_rows)
                round_idx += 1
                pending_rows = 0
                del pending[:], rr_offs[:]
                return True

            for b in source:
                rows = b.num_rows_host
                rr_offs.append(self._rr_offset)
                if self.partitioning == "roundrobin":
                    self._rr_offset = int((self._rr_offset + rows) % n)
                pending.append(b)
                pending_rows += rows
                if len(pending) == n and not try_flush():
                    break
            if not fell_back and pending:
                try_flush()
        except BaseException:
            for pieces in staged:
                for sp in pieces:
                    sp.close()
            raise
        if part_totals is not None:
            max_part = int(part_totals.max())
            self.metrics[PARTITION_SIZE].add(max_part)
            obs_events.emit("exchange", exec="HostShuffleExchangeExec",
                            op_id=self._op_id, partitions=n,
                            rounds=round_idx, lane="ici",
                            max_partition_rows=max_part,
                            partitioning=self.partitioning)
        if not fell_back:
            stats_rec.finish_and_emit()
            lifecycle.record_domain_success("ici_exchange")
            yield from self._yield_ici_partitions(staged, schema)
            return
        # hybrid drain: staged ICI rounds carry the EARLIER map
        # batches, the host lane the rest — chaining per partition
        # preserves the host lane's per-partition row order exactly.
        # The stats recorder (already holding the ICI rounds' map
        # records) rides into the host lane, which finish_and_emit()s
        # it once after its write phase: ONE exchange_stats record per
        # execution, whichever lanes it crossed.
        host_gens = self._execute_partitions_host(
            chain(iter(pending), source), stats_rec=stats_rec)
        yield from self._yield_ici_partitions(staged, schema,
                                              host_gens=host_gens)

    def _yield_ici_partitions(self, staged, schema, host_gens=None
                              ) -> "Iterator[Iterator[ColumnarBatch]]":
        """Hand out the per-partition drain generators with the host
        lane's abandonment protection: a NEVER-STARTED generator runs
        no finally even on close, so a weakref finalizer closes each
        partition's staged pieces (and their memory-budget
        reservations) when its generator is dropped undrained;
        partitions the consumer never reached — the outer generator
        closed early — close in the finally. SpillableBatch.close is
        idempotent, so overlapping the inline closes in _unspill_ici
        is safe. On the hybrid-drain path `host_gens` supplies the host
        lane's partition streams to chain after the staged pieces; it
        is closed on the way out so the host side's handle bookkeeping
        sees outer-done even when the consumer stops early."""
        import weakref

        def _close_pieces(pieces):
            for sp in pieces:
                sp.close()

        hg_it = iter(host_gens) if host_gens is not None else None
        handed = 0
        try:
            for p in range(self.n_partitions):
                if hg_it is None:
                    g = self._drain_ici_partition(staged[p], schema)
                else:
                    g = self._chain_ici_host(staged[p], schema,
                                             next(hg_it))
                weakref.finalize(g, _close_pieces, staged[p])
                handed += 1
                yield g
        finally:
            for q in range(handed, self.n_partitions):
                _close_pieces(staged[q])
            if host_gens is not None:
                host_gens.close()

    def _drain_ici_partition(self, pieces, schema
                             ) -> Iterator[ColumnarBatch]:
        out_rows = self.metrics[NUM_OUTPUT_ROWS]
        out_batches = self.metrics[NUM_OUTPUT_BATCHES]
        if not pieces:
            out_batches.add(1)
            yield empty_batch(schema)
            return
        stage = self.pipeline_stage(self._unspill_ici(pieces),
                                    "ici-read")
        try:
            for b in stage:
                out_batches.add(1)
                out_rows.add_device(b.num_rows)
                yield b
        finally:
            stage.close()

    @staticmethod
    def _unspill_ici(pieces) -> Iterator[ColumnarBatch]:
        """Unspill staged shard pieces one at a time (pipelined: piece
        k+1's promotion overlaps the consumer's compute on k); an early
        close drops the staged remainder's catalog entries."""
        it = iter(pieces)
        try:
            for sp in it:
                try:
                    b = sp.get_batch()
                    sp.release()
                except BaseException:
                    sp.close()
                    raise
                sp.close()
                yield b
        finally:
            for sp in it:
                sp.close()

    def _chain_ici_host(self, pieces, schema, host_gen
                        ) -> Iterator[ColumnarBatch]:
        """Fallback drain for one partition: the staged ICI pieces
        (earlier map batches) first, then the host lane's stream. The
        host generator always yields at least an empty batch, so the
        ICI side skips its own empty-partition padding."""
        out_rows = self.metrics[NUM_OUTPUT_ROWS]
        out_batches = self.metrics[NUM_OUTPUT_BATCHES]
        try:
            if pieces:
                stage = self.pipeline_stage(self._unspill_ici(pieces),
                                            "ici-read")
                try:
                    for b in stage:
                        out_batches.add(1)
                        out_rows.add_device(b.num_rows)
                        yield b
                finally:
                    stage.close()
            yield from host_gen
        finally:
            host_gen.close()

    def _make_recompute(self, handle, mgr, map_id: int):
        """Partition-granular recovery lineage (ISSUE 6): a zero-arg
        closure that re-executes ONLY this exchange's child sub-plan
        from its sources and atomically rewrites the one damaged map
        output — the engine analog of Spark recomputing a single lost
        map task instead of the whole job. Runs at shuffle READ time
        (possibly on the pipelined shuffle-read producer thread, which
        has adopted conf/query-id/attempt/lifecycle context); the
        round-robin offset is replayed from zero so the recomputed pid
        assignment is bit-identical to the original write."""

        def recompute() -> None:
            # serialization: the reader invokes lineage closures under
            # the handle's recover_lock (shuffle/manager.py), so two
            # corrupt map outputs read through the PIPELINED partition
            # streams never run this concurrently — the mutable
            # round-robin offset replay below relies on that
            saved_rr = self._rr_offset
            self._rr_offset = 0
            try:
                src = self.child.execute()
                try:
                    for i, b in enumerate(src):
                        n = b.num_rows_host
                        if i < map_id:
                            # skipped map tasks only advance the
                            # round-robin cursor; hash/single pids are
                            # stateless, so no device work is spent
                            if self.partitioning == "roundrobin":
                                self._rr_offset = int(
                                    (self._rr_offset + n)
                                    % self.n_partitions)
                            continue
                        # same lane as the original write (_write_map):
                        # the rewritten map output keeps the original
                        # frame layout, so the reader's frame index and
                        # the seeded chaos keys stay valid
                        self._write_map(b, n, None, handle, mgr,
                                        map_id, register=False)
                        return
                    raise RuntimeError(
                        f"partition recovery: child produced no "
                        f"batch {map_id} on re-execution")
                finally:
                    close = getattr(src, "close", None)
                    if close is not None:
                        close()
            finally:
                self._rr_offset = saved_rr

        return recompute

    def _read_partition(self, reader, p: int) -> Iterator[ColumnarBatch]:
        """Stream one partition's decoded blocks. Pipelined (ISSUE 3):
        the segment fetch + LZ4 decode of block k+1 run on the producer
        thread (over the reader pool) while the consumer computes on
        block k; shuffleReadTime counts only the time this operator
        BLOCKED waiting for a block, in both modes. Decoded blocks are
        HOST-backed (ISSUE 10): this seam promotes each to device as
        ONE packed upload, keyed per (partition, batch ordinal) for
        seeded chaos and attributed to numUploads/uploadPackTimeNs."""
        from ..columnar.upload import promote_stream
        read_time = self.metrics[SHUFFLE_READ_TIME]
        stage = self.pipeline_stage(
            promote_stream(reader.read_partition(p),
                           key_prefix=f"upload:p{p}", seam="shuffle",
                           num_metric=self.metrics[NUM_UPLOADS],
                           time_metric=self.metrics[UPLOAD_PACK_TIME]),
            "shuffle-read")
        saw = False
        try:
            while True:
                with read_time.ns_timer():
                    try:
                        b = next(stage)
                    except StopIteration:
                        break
                saw = True
                yield b
        finally:
            stage.close()
        if not saw:
            yield empty_batch(self.output_schema)

    # -- adaptive replanning (ISSUE 19) -------------------------------------
    def _adaptive_read_plan(self, stats_rec, reader, handle, flat):
        """The exchange-read consult point: decide skew splits (any
        consumer) and tiny-partition coalescing (flat consumers only)
        from the write phase's MEASURED per-partition bytes. Never
        raises — a consult failure records against the `adaptive`
        breaker domain and the static plan runs."""
        from . import adaptive
        op = type(self).__name__
        try:
            per_part = stats_rec.partition_bytes()
            if self.n_partitions <= 1 or per_part is None:
                return {}, None
            if not adaptive.consult(self._conf, op=op,
                                    op_id=self._op_id):
                return {}, None
            split_plan = {}
            thr = adaptive.skew_threshold(per_part, self._conf)
            if thr is not None and len(handle.map_outputs) > 1:
                threshold, median = thr
                for p, b in enumerate(per_part):
                    if b <= threshold:
                        continue
                    groups = reader.plan_map_groups(p, threshold)
                    if len(groups) <= 1:
                        continue
                    split_plan[p] = groups
                    adaptive.note_decision(
                        "skew_split", op=op, op_id=self._op_id,
                        partition=p, bytes=b, threshold=threshold,
                        median_bytes=median, subs=len(groups),
                        max_sub_bytes=max(g[1] for g in groups))
            flat_groups = None
            if flat:
                from ..config import ADAPTIVE_COALESCE_TARGET_BYTES
                target = self._conf.get(ADAPTIVE_COALESCE_TARGET_BYTES)
                if target > 0:
                    flat_groups = adaptive.coalesce_groups(
                        per_part, target, exclude=set(split_plan))
                    if flat_groups is not None:
                        adaptive.note_decision(
                            "partition_coalesce", op=op,
                            op_id=self._op_id,
                            partitions=self.n_partitions,
                            reads=len(flat_groups),
                            target_bytes=target)
            return split_plan, flat_groups
        except Exception as e:  # noqa: BLE001 — replan must not kill
            adaptive.note_error(op=op, op_id=self._op_id, error=e)
            return {}, None

    def _read_partition_split(self, reader, p: int, groups, handle,
                              ) -> Iterator[ColumnarBatch]:
        """A skew-split partition read (ISSUE 19 decision 1): K
        map-granular sub-reads in map order, each its own pipelined
        fetch/decode/promote stage, so the in-flight decode window is
        one sub-read (≤ the skew threshold) instead of the whole hot
        partition. Downstream, each promoted batch is one probe window
        against the replicated build side — concatenated output is
        byte-identical to the unsplit read."""
        from ..columnar.upload import promote_stream
        read_time = self.metrics[SHUFFLE_READ_TIME]
        ordinal = [0]
        saw = False
        for sub, (paths, _sub_bytes) in enumerate(groups):
            stage = self.pipeline_stage(
                promote_stream(
                    reader.read_partition_maps(p, paths, sub, ordinal),
                    key_prefix=f"upload:p{p}", seam="shuffle",
                    num_metric=self.metrics[NUM_UPLOADS],
                    time_metric=self.metrics[UPLOAD_PACK_TIME]),
                "shuffle-read")
            try:
                while True:
                    with read_time.ns_timer():
                        try:
                            b = next(stage)
                        except StopIteration:
                            break
                    saw = True
                    yield b
            finally:
                stage.close()
        if not saw:
            yield empty_batch(self.output_schema)

    def node_description(self):
        return (f"HostShuffleExchangeExec[n={self.n_partitions}, "
                f"keys={self.partition_exprs!r}]")


class BroadcastExchangeExec(TpuExec):
    """Materialize the child once as a single device-resident batch and
    replay it to every consumer execution (reference
    GpuBroadcastExchangeExec.scala:352: the build side is collected,
    serialized once, and kept device-resident on every executor).

    On a TPU mesh the replication itself is free at this layer: the batch
    lives in HBM and multi-chip consumers read it replicated (an
    all-gather-free broadcast — the stream side never moves at all, which
    is the entire point of a broadcast join)."""

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._materialized: Optional[ColumnarBatch] = None

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def additional_metrics(self):
        return (BROADCAST_TIME, (PARTITION_SIZE, ESSENTIAL))

    def _fingerprint_extras(self):
        # stateless pass-through at the program level (materialization
        # is host-side concat via module sites): extras exist so parent
        # subtrees over a broadcast build side stay cacheable
        return ()

    def materialize(self) -> ColumnarBatch:
        if self._materialized is None:
            with self.metrics[BROADCAST_TIME].ns_timer():
                batches = list(self.child.execute())
                if not batches:
                    self._materialized = empty_batch(self.output_schema)
                elif len(batches) == 1:
                    self._materialized = batches[0]
                else:
                    self._materialized = concat_batches(
                        batches, self.output_schema)
            size = self._materialized.device_size_bytes()
            self.metrics[PARTITION_SIZE].add(size)
            obs_events.emit("exchange", exec="BroadcastExchangeExec",
                            op_id=self._op_id, bytes=size)
        return self._materialized

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        yield self.materialize()

    def node_description(self):
        return "BroadcastExchangeExec"


class ShuffledHashJoinExec(TpuExec):
    """Per-partition hash join over two shuffle exchanges (reference
    GpuShuffledHashJoinExec.scala). Both children are hash-partitioned on
    the join keys with the SAME partitioning, so rows with equal keys
    colocate on one shard; the union of per-partition joins is globally
    exact — including outer sides, because an unmatched row can only ever
    match within its own partition.

    One inner HashJoinExec instance is reused across partitions (its jit
    caches key on batch shapes, which repeat across shards)."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = "inner",
                 build_side: str = "right",
                 condition: Optional[Expression] = None):
        super().__init__(left, right)
        from .joins import HashJoinExec
        self.join_type = join_type
        self._lscan = _ReplayScanExec(left.output_schema)
        self._rscan = _ReplayScanExec(right.output_schema)
        self._join = HashJoinExec(self._lscan, self._rscan, left_keys,
                                  right_keys, join_type,
                                  build_side=build_side, condition=condition)

    @property
    def output_schema(self) -> Schema:
        return self._join.output_schema

    @property
    def partitions_on_chips(self) -> bool:
        # both sides come from mesh exchanges: partition p of each sits on
        # chip p, and so does what the join makes of them
        return all(c.partitions_on_chips for c in self.children)

    def _fingerprint_extras(self):
        # the per-partition join's own programs, keyed by its fingerprint
        # (its leaves are replay scans, which depend on their schema alone)
        inner = self._join.plan_fingerprint()
        return None if inner is None else ("shuffled_hash_join", inner)

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        for part in self.execute_partitions():
            yield from part

    def execute_partitions(self) -> Iterator[Iterator[ColumnarBatch]]:
        """One lazy batch-generator per partition pair, in partition
        order; each must be drained before the next starts (one inner
        join serves them all)."""
        # lazy zip over PARTITION STREAMS: only one partition pair is
        # resident at a time, and within it the stream side's pieces
        # flow through the inner join one batch at a time (round 5 —
        # a skewed shard is no longer concatenated whole; the build side
        # still materializes its partition, as any hash build must)
        build_right = self._join.build_side == "right"
        # adaptive skew shield (ISSUE 19): arm the STREAM-side host
        # exchange — its skewed partitions split into sub-read probe
        # streams against this join's replicated per-partition build,
        # and an armed splitter keeps that exchange off the ICI lane
        stream_child = self.children[0] if build_right \
            else self.children[1]
        build_child = self.children[1] if build_right \
            else self.children[0]
        if isinstance(stream_child, HostShuffleExchangeExec):
            stream_child._adaptive_probe_split = True
        # single-build conversion (ISSUE 19 decision 2, converse): when
        # the build side's exchange MEASURES small at write time, the
        # per-partition zip collapses to one single-build probe pass —
        # the build replays whole (it fits by measurement) and the
        # probe side's exchange is skipped entirely (its subtree
        # streams straight into the probe). Correct because the
        # partitioned join's union is the whole join; only row order
        # changes.
        build_gens = None
        if isinstance(stream_child, HostShuffleExchangeExec) \
                and isinstance(build_child, HostShuffleExchangeExec):
            from . import adaptive
            from ..config import ADAPTIVE_ENABLED
            conf = build_child._conf
            cap = adaptive.auto_broadcast_max(conf) \
                if conf.get(ADAPTIVE_ENABLED) else -1
            if cap >= 0 and adaptive.consult(
                    conf, op=type(self).__name__, op_id=self._op_id):
                build_gens = list(build_child.execute_partitions())
                measured = build_child._adaptive_write_bytes
                if measured is not None and measured <= cap:
                    adaptive.note_decision(
                        "single_build_convert", op=type(self).__name__,
                        op_id=self._op_id, measured_bytes=measured,
                        threshold=cap)
                    batches = [b for g in build_gens for b in g]
                    probe = stream_child.child.execute()
                    if build_right:
                        self._rscan._batches = batches
                        self._lscan.set_stream(probe)
                    else:
                        self._lscan._batches = batches
                        self._rscan.set_stream(probe)
                    yield self._join.execute()
                    return
        if build_gens is None:
            lit_ = self.children[0].execute_partitions()
            rit = self.children[1].execute_partitions()
        elif build_right:
            lit_ = self.children[0].execute_partitions()
            rit = iter(build_gens)
        else:
            lit_ = iter(build_gens)
            rit = self.children[1].execute_partitions()
        while True:
            lp = next(lit_, None)
            rp = next(rit, None)
            if (lp is None) != (rp is None):
                raise AssertionError(
                    "both sides must use the same partitioning")
            if lp is None:
                return
            yield self._join_partition(lp, rp, build_right)

    def _join_partition(self, lp, rp, build_right: bool
                        ) -> Iterator[ColumnarBatch]:
        if build_right:
            self._lscan.set_stream(lp)
            self._rscan._batches = list(rp)
        else:
            self._lscan._batches = list(lp)
            self._rscan.set_stream(rp)
        yield from self._join.execute()

    def node_description(self):
        return f"ShuffledHashJoinExec[{self.join_type}]"


class _ReplayScanExec(TpuExec):
    """Leaf fed per partition by ShuffledHashJoinExec: either a
    materialized batch list (`_batches`, for the build side) or a lazy
    one-shot generator (`set_stream`, for the stream side — pieces flow
    through the join without whole-shard concatenation)."""

    def __init__(self, schema: Schema):
        super().__init__()
        self._schema = schema
        self._batches: List[ColumnarBatch] = []
        self._stream = None

    def set_stream(self, gen) -> None:
        self._stream = gen
        self._batches = []

    def _fingerprint_extras(self):
        # what it replays is data: the programs over it depend on the
        # schema alone, which the fingerprint holds
        return ()

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        if self._stream is not None:
            gen, self._stream = self._stream, None
            yield from gen
            return
        yield from self._batches
