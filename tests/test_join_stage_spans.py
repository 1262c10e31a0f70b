"""What ISSUE 31 added around the fused join stage (`CompiledStageExec`,
kind `join_agg`), on a Q14-shaped query over Parquet with a string column:
the answer is the same whatever the size cache went through (cold, warm, an
expired entry, caps kept from a larger table), the cache's misses and
refreshes are counted, the spans `join.build` and `join.sizing` are host
events that accrue their phases with the books still closed, the
dictionary decode's programs carry ledger labels, and `lit(datetime.date)`
can be evaluated."""

import datetime
import glob

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import ColumnarBatch
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import stage_compiler
from spark_rapids_tpu.exec.joins import HashJoinExec
from spark_rapids_tpu.expr import resolve
from spark_rapids_tpu.expr.conditional import CaseWhen
from spark_rapids_tpu.expr.core import Literal, col, lit
from spark_rapids_tpu.obs import dispatch, events, phase
from spark_rapids_tpu.obs.phase import PHASES
from spark_rapids_tpu.types import DATE, Schema

TYPES = ("PROMO TIN", "PROMO BRASS", "LARGE TIN", "SMALL STEEL", "ECONOMY TIN")
LO, HI = 9374, 9404            # 1995-09-01, 1995-10-01 as days since 1970


@pytest.fixture(autouse=True)
def _isolation():
    dispatch.reset_dispatch_ledger()
    stage_compiler.reset_stage_counters()
    phase.reset_phase_counters()
    yield
    dispatch.reset_dispatch_ledger()
    stage_compiler.reset_stage_counters()
    phase.reset_phase_counters()
    events.reset_event_bus()
    TpuSession()  # restore the default active conf


def _tables(tmp_path, seed=3, parts=300, lines=4000):
    rng = np.random.default_rng(seed)
    d = tmp_path / f"s{seed}_{lines}"
    (d / "part").mkdir(parents=True)
    (d / "lineitem").mkdir()
    ptype = [TYPES[i] for i in rng.integers(0, len(TYPES), parts)]
    pq.write_table(pa.table({
        "p_partkey": pa.array(np.arange(1, parts + 1), pa.int64()),
        "p_type": pa.array(ptype)}), str(d / "part" / "p.parquet"))
    key = rng.integers(1, parts + 1, lines)
    price = rng.integers(100, 5000, lines) / 1.0
    disc = rng.integers(0, 11, lines) / 100.0
    ship = rng.integers(LO - 60, HI + 60, lines).astype(np.int32)
    for i in range(2):
        sl = slice(i * lines // 2, (i + 1) * lines // 2)
        pq.write_table(pa.table({
            "l_partkey": pa.array(key[sl], pa.int64()),
            "l_extendedprice": pa.array(price[sl], pa.float64()),
            "l_discount": pa.array(disc[sl], pa.float64()),
            "l_shipdate": pa.array(ship[sl], pa.date32())}),
            str(d / "lineitem" / f"l{i}.parquet"),
            row_group_size=lines // 4)
    month = (ship >= LO) & (ship < HI)
    rev = price[month] * (1.0 - disc[month])
    promo = np.array([ptype[k - 1].startswith("PROMO") for k in key[month]])
    want = 100.0 * rev[promo].sum() / rev.sum()
    return str(d / "lineitem"), str(d / "part"), want


def _q14(sess, lineitem, part):
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    promo = CaseWhen([(F.like(col("p_type"), "PROMO%"), rev)], lit(0.0))
    return (sess.read_parquet(lineitem)
            .filter((col("l_shipdate") >= Literal(LO, DATE))
                    & (col("l_shipdate") < Literal(HI, DATE)))
            .join(sess.read_parquet(part), left_on=col("l_partkey"),
                  right_on=col("p_partkey"))
            .select(promo.alias("promo"), rev.alias("rev"))
            .agg((F.sum(col("promo")), "promo"), (F.sum(col("rev")), "rev"))
            .select((lit(100.0) * col("promo") / col("rev")).alias("r")))


def _sizings():
    return sum(p["dispatches"] for p in dispatch.programs()
               if p["label"] == "CompiledStageExec.sizing")


def _collect(sess, paths):
    ((got,),) = _q14(sess, paths[0], paths[1]).collect()
    assert got == pytest.approx(paths[2], rel=1e-12)
    return got


# -- the size cache ------------------------------------------------------------

def test_the_plan_is_one_fused_join_stage(tmp_path):
    paths = _tables(tmp_path)
    plan = _q14(TpuSession(), paths[0], paths[1])._exec()
    stages = []

    def walk(node):
        if isinstance(node, stage_compiler.CompiledStageExec):
            stages.append(node._kind)
        for c in node.children:
            walk(c)
    walk(plan)
    assert "join_agg" in stages


def test_the_answer_is_the_same_cold_warm_and_after_an_expiry(
        tmp_path, monkeypatch):
    paths = _tables(tmp_path)
    sess = TpuSession()
    cold = _collect(sess, paths)
    assert _sizings() == 1                         # the cold shape sized once
    c = stage_compiler.counters()
    assert (c["size_cache_misses"], c["size_cache_refreshes"]) == (1, 0)
    warm = [_collect(sess, paths) for _ in range(2)]
    assert _sizings() == 1                         # warm: no sizing, no sync
    # an entry expires after SPEC_REFRESH uses and is measured again
    monkeypatch.setattr(HashJoinExec, "SPEC_REFRESH", 1)
    after = [_collect(sess, paths) for _ in range(4)]
    c = stage_compiler.counters()
    assert c["size_cache_misses"] == 1 and c["size_cache_refreshes"] >= 1
    assert _sizings() == 1 + c["size_cache_refreshes"]
    assert set(warm + after) == {cold}             # bit for bit


def test_caps_kept_from_a_larger_table_give_the_same_answer(tmp_path):
    """The size cache is shared by plan fingerprint and keyed by the two
    capacities: a process that has seen more matching rows under the same
    capacities keeps the larger caps. The answer of the smaller table is
    what a fresh process gives."""
    small = _tables(tmp_path, seed=4, lines=4000)
    sess = TpuSession()
    fresh = _collect(sess, small)
    stage_compiler.reset_stage_counters()          # forget the caps
    dispatch.reset_dispatch_ledger()
    # same capacities (4000 rows a file pair), every row inside the month
    dense = _tables(tmp_path, seed=5, lines=4000)
    lineitem = pq.read_table(dense[0]).to_pandas()
    lineitem["l_shipdate"] = datetime.date(1995, 9, 15)
    for f in glob.glob(dense[0] + "/*.parquet"):
        pq.write_table(pa.Table.from_pandas(
            lineitem.iloc[:2000], preserve_index=False).cast(pa.schema([
                ("l_partkey", pa.int64()), ("l_extendedprice", pa.float64()),
                ("l_discount", pa.float64()), ("l_shipdate", pa.date32())])),
            f, row_group_size=1000)
    _q14(sess, dense[0], dense[1]).collect()       # leaves its larger caps
    assert _sizings() == 1
    assert _collect(sess, small) == fresh
    assert _sizings() == 1                         # served by those caps


# -- the spans -----------------------------------------------------------------

def test_join_build_and_join_sizing_accrue_their_phases(tmp_path):
    paths = _tables(tmp_path)
    sess = TpuSession()
    before = phase.counters()
    _collect(sess, paths)                          # cold: sizes once
    cur = phase.counters()
    assert cur["join-build"] > before["join-build"]
    assert cur["device-wait"] > before["device-wait"]
    prof = sess.last_query_profile()
    led = prof._phase_ledger
    assert led._direct.get("join-build", 0) > 0    # the driving thread's
    ph = prof.phases()
    assert set(ph) == set(PHASES)
    assert ph["join-build"] == led._direct["join-build"]
    assert sum(ph.values()) == prof.phases_wall_ns()
    assert min(ph.values()) >= 0
    # exclusive: the build child's stalls are not booked twice
    assert ph["join-build"] + ph["pipeline-stall"] <= prof.phases_wall_ns()


def test_the_joins_spans_are_host_events_in_a_profiler_trace(tmp_path):
    paths = _tables(tmp_path)
    sess = TpuSession()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _collect(sess, paths)                      # cold: build and sizing
        _collect(sess, paths)                      # warm: build only
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                          recursive=True)
    seen = {"join.build": 0, "join.sizing": 0}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in seen and e.duration_ns > 0:
                    seen[e.name] += 1
    assert seen == {"join.build": 2, "join.sizing": 1}


def test_every_program_of_the_query_has_a_ledger_label(tmp_path):
    """The dictionary decode of `p_type` at the stage's boundary ran
    eagerly, past the ledger; its two programs carry labels now."""
    paths = _tables(tmp_path)
    _collect(TpuSession(), paths)
    labels = {p["label"] for p in dispatch.programs()}
    assert {"encoded.decode", "encoded.decoded_bytes",
            "CompiledStageExec.probe_step",
            "CompiledStageExec.sizing"} <= labels
    ml = dispatch.module_labels()
    assert ml["jit__decode"] == ["encoded.decode"]
    assert ml["jit__decoded_bytes"] == ["encoded.decoded_bytes"]
    assert ml["jit__ja_spec_body"] == ["CompiledStageExec.probe_step"]


# -- lit(datetime.date) --------------------------------------------------------

def test_a_date_literal_can_be_evaluated():
    d = lit(datetime.date(1995, 9, 1))
    assert d.data_type == DATE and d.value == LO
    assert lit(datetime.date(1970, 1, 1)).value == 0
    b = ColumnarBatch.from_pydict({"d": [LO - 1, LO, HI, None]},
                                  Schema.of(d=DATE))
    e = resolve((col("d") >= d) & (col("d") < lit(datetime.date(1995, 10, 1))),
                b.schema)
    assert e.columnar_eval(b).to_pylist(4) == [False, True, False, None]


# -- the probe's payload sizes -------------------------------------------------

def test_a_ranges_payload_size_comes_with_its_row_of_the_pair_table():
    """`probe_ranges` reads each stream row's candidate payload size from
    the pair table's own row; it equals the prefix sums of the build
    rows' sizes in sorted order taken at the range's two ends, which is
    how the output was sized before (by four gathers a stream row)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.join import BuildTable, probe_ranges
    from spark_rapids_tpu.types import LONG, STRING
    rng = np.random.default_rng(9)
    keys = [int(k) for k in rng.integers(0, 40, 200)] + [None] * 5
    strs = [TYPES[int(i)] * int(n) for i, n in
            zip(rng.integers(0, 5, 205), rng.integers(0, 4, 205))]
    strs[7] = None
    payload = {"s": strs, "t": strs[::-1]}
    b = ColumnarBatch.from_pydict({"k": keys, **payload},
                                  Schema.of(k=LONG, s=STRING, t=STRING))
    table = BuildTable.build([b.columns[0]], list(b.columns), b.num_rows,
                             b.capacity)
    assert table.pair_table.shape[1] == 2 + 2     # two string payloads
    assert table.pair_table.dtype == jnp.int32
    probe = ColumnarBatch.from_pydict(
        {"k": [int(k) for k in rng.integers(-5, 60, 300)] + [None]},
        Schema.of(k=LONG))
    lo, counts, valid, sizes = probe_ranges(
        table, [probe.columns[0]], probe.num_rows, probe.capacity)
    lo, counts = np.asarray(lo), np.asarray(counts)
    assert len(sizes) == 2 and counts.sum() > 0
    perm = np.asarray(table.perm)
    n_valid = int(table.valid_count)
    assert n_valid == 200                          # the NULL keys are out
    for size, column in zip(sizes, payload.values()):
        lens = np.zeros(b.capacity, np.int64)
        lens[:205] = [len(v or "") for v in column]
        in_order = np.where(np.arange(b.capacity) < n_valid, lens[perm], 0)
        prefix = np.concatenate([[0], np.cumsum(in_order)])
        assert (np.asarray(size) == prefix[lo + counts] - prefix[lo]).all()
        assert int(np.asarray(size).sum()) > 0
