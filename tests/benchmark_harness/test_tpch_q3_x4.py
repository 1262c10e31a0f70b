"""CPU tests of what the configuration `tpch-q3-x4` and its cell `q3_join_x4`
add: TPC-H Q3 through a session whose conf spans four chips (here four of
the conftest's virtual CPU devices) against the plain reference at the
rehearsal size, the plan it builds, partitions as arrays of their own
device, a warm query that traces nothing, the refusal of a program that
plans the one-chip query, and the three new readers. The cell-parametrised
tests of `test_benchmark_harness.py` pick the cell up from `BENCHMARK.json`
by themselves. None of this is a chip run."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import datagen, peaks, trace  # noqa: E402
from benchmarks.lib.exchange_programs import EXCHANGE_PREFIX  # noqa: E402
from benchmarks.lib.manifest import Manifest, apply_rehearsal  # noqa: E402
from appending import lists_the_cell  # noqa: E402

CELL = "q3_join_x4"


@pytest.fixture(scope="module")
def config():
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(CELL)["config"]))
    return cfg, m.config_module(cfg, "reference"), m.config_module(cfg, "query")


@pytest.fixture(scope="module")
def seeded(config, tmp_path_factory):
    """{seed: (tables, paths)} at the rehearsal size, written once."""
    cfg, ref, _ = config
    out = {}
    for seed in (3000000019, 7):
        tables = ref.generate(seed, cfg)
        out[seed] = tables, datagen.write_tables(
            str(tmp_path_factory.mktemp(f"q3x4_{seed}")), tables,
            cfg["schema"], cfg["layout"])
    return out


@pytest.fixture
def session(config):
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    cfg, _, query = config
    query._counted = None       # what moved is judged within a test
    sess = TpuSession(dict(cfg["session_conf"]))
    yield sess
    set_active_mesh(None)


def _traces():
    from spark_rapids_tpu.obs import dispatch
    return dispatch.counters()["traces"]


# -- the manifest --------------------------------------------------------------

REPORTS = ["ingest_dispatches", "ingest_stall_ms", "concat_busy_share",
           "stage_dispatches", "compile_s", "window_compiles",
           "query_hbm_share", "device_idle_share", "hbm_peak_gib",
           "plan_span_ms", "scan_decode_ms", "upload_ms", "device_wait_ms",
           "ingest_busy_share", "labelled_busy_share", "direct_pack_share",
           "host_syncs", "host_sync_ms", "join_busy_share", "join_build_ms",
           "groupby_ms", "sort_ms", "groupby_fallbacks", "decode_busy_share",
           # the speculation scope and the top-N run here as in the one-chip
           # cell, whose metrics these are
           "plan_reruns", "topn_busy_share"]
ITS_OWN = ["exchange_ms", "exchange_busy_share", "exchange_roofline",
           "exchange_spread_share"]
NOT_THE_CELLS = ["agg_stage_roofline", "join_probe_roofline",
                 "join_sizing_dispatches", "groupby_busy_share",
                 "groupby_update_roofline", "groupby_lane_share",
                 "groupby_mask_share",
                 # its programs run here, but the reader sets ONE chip's HBM
                 # peak against the time of the MEAN chip, while the query's
                 # bytes are spread over four (as `query_hbm_share` does): a
                 # four-chip cell reads it once the reader counts the chips
                 "join_groupby_roofline"]


def test_the_manifest_names_the_cell_on_four_chips_and_its_configuration(doc):
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch-q3-x4", "closed1", 4)
    config = {c["name"]: c for c in doc["configs"]}["tpch-q3-x4"]
    assert config["file"] == "benchmarks/configs/tpch-q3-x4/config.json"
    assert "clause 2.4.3 Q3" in config["source"] and not config["reduced"]
    assert "four chips" in config["source"]


@pytest.mark.parametrize("metric", REPORTS + ITS_OWN + NOT_THE_CELLS)
def test_the_cell_is_on_the_list_of_every_metric_it_reports(doc, metric):
    lists_the_cell(doc, CELL, metric, ITS_OWN, NOT_THE_CELLS)


def test_the_configuration_is_tpch_q3_on_four_partitions(config):
    """The same tables, query and comparison as `tpch-q3`; the conf asks
    for the ICI shuffle over four partitions and the default broadcast
    threshold, scaled in the rehearsal as `tpch-q3`'s is."""
    m = Manifest()
    q3 = m.config("tpch-q3")
    cfg = m.config("tpch-q3-x4")
    for key in ("scale", "schema", "layout", "params", "query", "precision",
                "limits", "proofs", "rehearse"):
        assert cfg[key] == q3[key], key
    assert cfg["partitions"] == 4 and cfg["reduced"] == {}
    assert cfg["session_conf"] == {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.sql.shuffle.partitions": "4",
        "spark.rapids.sql.broadcastSizeThreshold": "10485760"}
    assert set(q3["guarantees"]) < set(cfg["guarantees"])
    rehearsed, _, _ = config
    assert rehearsed["session_conf"][
        "spark.rapids.sql.broadcastSizeThreshold"] == "104857"


def test_the_work_model_adds_the_least_bytes_between_chips(config, seeded):
    """`tpch-q3`'s bytes, and beside them what any four-chip plan must move:
    the customers of the segment and the orders they placed before the date
    sent to three other chips, three quarters of the groups."""
    cfg, ref, _ = config
    tables, _ = seeded[7]
    work = ref.work_model(cfg, tables)
    q3_work = ref.q3.work_model(cfg, tables)
    assert set(work) == {"join_groupby"}
    entry = work["join_groupby"]
    assert {k: v for k, v in entry.items() if k != "ici_bytes"} == \
        q3_work["join_groupby"]
    cust, orders = tables["customer"], tables["orders"]
    seg = np.asarray(cust["c_custkey"])[
        np.asarray(cust["c_mktsegment"], dtype=object) == "BUILDING"]
    placed = np.isin(orders["o_custkey"][
        orders["o_orderdate"] < ref.q3.cut_date(cfg)], seg).sum()
    groups = ref.reference(tables, cfg)["groups"]
    assert entry["ici_bytes"] == int(len(seg) * 8 * 3 + placed * 16 * 3
                                     + groups * 24 * 3 / 4)


# -- the session's mesh from its conf ------------------------------------------

def test_mode_ici_spans_the_partitions_and_other_modes_no_mesh():
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.mesh import active_mesh, set_active_mesh
    try:
        sess = TpuSession({"spark.rapids.shuffle.mode": "ICI",
                           "spark.rapids.sql.shuffle.partitions": "4"})
        assert sess.mesh.size == 4 and active_mesh() is sess.mesh
        assert TpuSession({"spark.rapids.sql.shuffle.partitions": "4"}
                          ).mesh is None
        assert TpuSession().mesh is None and active_mesh() is None
    finally:
        set_active_mesh(None)


def test_mode_ici_with_more_partitions_than_devices_raises():
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    n = len(jax.devices())
    try:
        with pytest.raises(ValueError, match=f"{n + 1} devices.*{n} are"):
            TpuSession({"spark.rapids.shuffle.mode": "ICI",
                        "spark.rapids.sql.shuffle.partitions": str(n + 1)})
    finally:
        set_active_mesh(None)


# -- the query against the reference -------------------------------------------

def test_the_plan_exchanges_over_four_partitions_with_a_fingerprint(
        config, seeded, session):
    cfg, _, query = config
    _, paths = seeded[7]
    plan = query.build(session, paths, cfg)._exec()
    text = plan.tree_string()
    assert text.count("ShuffleExchangeExec[n=4") == 3
    assert "ShuffledHashJoinExec" in text
    assert "AggregateExec[final" in text and "agg:" in text
    assert plan.plan_fingerprint() is not None


@pytest.mark.parametrize("seed", [3000000019, 7])
def test_q3_on_four_partitions_is_the_references_ten_rows(
        config, seeded, session, seed):
    """Every collect answers as the reference does, the second and later
    trace no program, and the float32 control fails the revenues' limit."""
    cfg, ref, query = config
    tables, paths = seeded[seed]
    answer = ref.reference(tables, cfg)
    for n in range(3):
        before = _traces()
        rows = query.build(session, paths, cfg).collect()
        got = ref.compare(rows, answer)
        assert got["rows_wrong"] == 0, (rows, answer)
        assert got["sum_rel_err"] <= cfg["limits"]["sum_rel_err"]
        if n:
            assert _traces() == before
    low = ref.compare(ref.as_rows(ref.reference(tables, cfg, np.float32)),
                      answer)
    assert low["sum_rel_err"] > 30 * cfg["limits"]["sum_rel_err"]


def test_each_partition_after_an_exchange_is_on_a_device_of_its_own(
        config, seeded, session):
    """Partition i of every exchange is an array on mesh device i alone:
    the programs downstream run on that one device."""
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    cfg, _, query = config
    _, paths = seeded[7]
    seen = []
    drain = ShuffleExchangeExec._drain_partition

    def watch(self, pieces, schema):
        for b in drain(self, pieces, schema):
            import jax
            seen.append((self._op_id, {d for leaf in jax.tree_util.tree_leaves(b)
                                       for d in leaf.devices()}))
            yield b

    mesh = session.mesh
    ShuffleExchangeExec._drain_partition = watch
    try:
        query.build(session, paths, cfg).collect()
    finally:
        ShuffleExchangeExec._drain_partition = drain
    by_exchange = {}
    for op, devs in seen:
        assert len(devs) == 1, devs
        by_exchange.setdefault(op, []).append(next(iter(devs)))
    assert len(by_exchange) == 3
    for devs in by_exchange.values():
        assert devs == list(mesh.devices.flat)


def test_only_the_top_n_moves_rows_between_chips_outside_the_exchanges(
        config, seeded, session, monkeypatch):
    """A warm Q3 moves between chips, outside its three exchanges, only the
    top-10's last selection: the first rows of partitions 1-3, one batch of
    the smallest bucket each, onto partition 0's chip, and only from the
    sort's merge."""
    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.exec import exchange
    cfg, _, query = config
    _, paths = seeded[7]
    query.build(session, paths, cfg).collect()
    callers = []
    moves = exchange.onto_one_chip

    def spy(batches):
        callers.append(sys._getframe(1).f_code.co_qualname)
        return moves(batches)
    monkeypatch.setattr(exchange, "onto_one_chip", spy)
    before = exchange.counters()
    query.build(session, paths, cfg).collect()
    after = exchange.counters()
    moved = {k: after[k] - before[k]
             for k in ("batches_moved", "rows_moved", "bytes_moved")}
    assert moved["batches_moved"] == 3
    assert moved["rows_moved"] == 3 * bucket_capacity(10)
    assert 0 < moved["bytes_moved"] < 3 * bucket_capacity(10) * 64
    assert set(callers) == {"SortExec._merge.<locals>.do"}
    # ... and the query module, between two queries, judges so
    query.build(session, paths, cfg)


def test_a_query_that_moved_partitions_between_chips_is_refused(config):
    """What the query module refuses: more moved between chips, outside the
    exchanges, than the top-10's last selection, over the plan's runs."""
    from spark_rapids_tpu.exec import aggregate, exchange
    cfg, _, query = config
    now = (exchange.counters(), aggregate.counters())
    fewer = dict(now[0], batches_moved=now[0]["batches_moved"] - 3,
                 rows_moved=now[0]["rows_moved"] - 3 * 128)
    query._counted = (fewer, now[1])
    query.check_only_the_top_n_moved(4, 10)             # the top-N alone
    query._counted = (dict(fewer, batches_moved=fewer["batches_moved"] - 1),
                      now[1])
    with pytest.raises(RuntimeError, match="processed on a chip not its own"):
        query.check_only_the_top_n_moved(4, 10)
    twice = dict(now[1], plan_reruns=now[1]["plan_reruns"] - 1)
    query._counted = (dict(fewer, batches_moved=fewer["batches_moved"] - 3,
                           rows_moved=fewer["rows_moved"] - 3 * 128), twice)
    query.check_only_the_top_n_moved(4, 10)             # a rerun: twice
    query._counted = None


def test_a_session_without_a_mesh_is_refused_before_the_first_scan(
        config, seeded, monkeypatch):
    """The parent's session took no mesh from its conf and planned the
    one-chip Q3: refused from the plan alone, once a process, with the plan
    and the reason in the words; nothing ran."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs import dispatch
    cfg, _, query = config
    _, paths = seeded[7]
    monkeypatch.setattr(query, "_plan_checked", False)
    sess = TpuSession({"spark.rapids.sql.broadcastSizeThreshold": "104857"})
    before = dispatch.counters()["dispatches"]
    with pytest.raises(RuntimeError, match="exchanges over no partitions"
                       ) as e:
        query.build(sess, paths, cfg)
    assert "fewer than the 4 chips" in str(e.value)
    assert "AdaptiveJoinExec" in str(e.value)
    assert dispatch.counters()["dispatches"] == before
    assert query._plan_checked is False


def test_a_mesh_plan_without_a_fingerprint_is_refused(
        config, seeded, session, monkeypatch):
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    cfg, _, query = config
    _, paths = seeded[7]
    monkeypatch.setattr(query, "_plan_checked", False)
    monkeypatch.setattr(ShuffleExchangeExec, "_fingerprint_extras",
                        lambda self: None)
    with pytest.raises(RuntimeError, match="without a plan fingerprint"):
        query.build(session, paths, cfg)
    monkeypatch.undo()
    monkeypatch.setattr(query, "_plan_checked", False)
    query.build(session, paths, cfg)
    assert query._plan_checked is True


# -- the three readers ---------------------------------------------------------

class Obs:
    """A hand-made observation: what `lib/observe.Observation` carries."""

    def __init__(self, queries=4, phases=None, trace=None, work=None,
                 device_peaks=None):
        self.queries = queries
        self.window = {"families": {}, "phases": phases or {}, "labels": {}}
        self.trace = trace
        self.work = work or {}
        self.peaks = peaks.peaks_for("TPU v5 lite") if device_peaks is None \
            else device_peaks


MODULES = {"jit__exchange_step": ["ShuffleExchangeExec.exchange_step"],
           "jit__measure_kernel": ["ShuffleExchangeExec.measure"],
           "jit__stack_piece": ["ShuffleExchangeExec.stack"],
           "jit__probe_kernel": ["HashJoinExec.probe"]}


@pytest.fixture
def module_labels(monkeypatch):
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


def _reduced(module_s, busy_s=4.0, chips=4):
    return trace.Reduced(chips, 5.0, busy_s, dict(module_s),
                         {m: 2 for m in module_s}, [], [])


def test_exchange_ms_reads_the_ici_collective_phase():
    read = Manifest().reader("exchange_ms")
    assert read(Obs(4, {"ici-collective": 8e6})) == pytest.approx(2.0)
    assert read(Obs(4, {"ici-collective": 0})) == 0.0
    assert read(Obs(4, {})) is None                 # the parent's program
    assert read(Obs(0, {"ici-collective": 8e6})) is None


def test_exchange_busy_share_and_roofline_find_the_exchange_by_label(
        module_labels):
    m = Manifest()
    busy, roofline = m.reader("exchange_busy_share"), \
        m.reader("exchange_roofline")
    assert all(v[0].startswith(EXCHANGE_PREFIX)
               for k, v in MODULES.items() if k != "jit__probe_kernel")
    red = _reduced({"jit__exchange_step": 0.3, "jit__measure_kernel": 0.1,
                    "jit__stack_piece": 0.1, "jit__probe_kernel": 1.0})
    module_labels(MODULES)
    assert busy(Obs(trace=red)) == pytest.approx(100 * 0.5 / 4.0)
    # 8e9 bytes between chips at 200 GB/s a chip on four chips: 10 ms a
    # query, 4 queries against 0.5 s of the exchange's programs
    work = {"join_groupby": {"bytes": 1, "bound": "memory",
                             "ici_bytes": 8e9}}
    assert roofline(Obs(trace=red, work=work)) == pytest.approx(
        100 * 0.01 * 4 / 0.5)
    for read in (busy, roofline):
        assert read(Obs(trace=None, work=work)) is None
        assert read(Obs(trace=_reduced({"jit__probe_kernel": 1.0}),
                        work=work)) is None          # silent, never 0
    assert roofline(Obs(trace=red, work={"join_groupby": {"bytes": 1}})) \
        is None
    assert roofline(Obs(trace=red, work=work, device_peaks={})) is None
    module_labels({"jit__exchange_step": ["ShuffleExchangeExec.exchange_step",
                                          "HostShuffleExchangeExec.split"]})
    assert busy(Obs(trace=red)) is None             # a module it cannot split
    module_labels({})
    assert busy(Obs(trace=red)) is None and \
        roofline(Obs(trace=red, work=work)) is None
