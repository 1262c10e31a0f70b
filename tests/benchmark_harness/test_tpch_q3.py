"""CPU tests of what the configuration `tpch-q3` and its cell
`q3_join_groupby_topn` add (ISSUE 38): the generator of the three tables Q3
reads, the session path against the plain reference at the rehearsal size
(the plan the planner builds at SF1: an adaptive join over a join, a
group-by of more groups than the masked buckets hold, a top-N), the
comparison's rule for tied revenues, the three new readers, and the check
that refuses a program whose plan has no fingerprint. The cell-parametrised
tests of `test_benchmark_harness.py` pick the cell up from `BENCHMARK.json`
by themselves. None of this is a chip run."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import datagen, dbgen, dbgen_q3, peaks, trace  # noqa: E402
from benchmarks.lib.manifest import (Manifest, apply_rehearsal,  # noqa: E402
                                     manifest_at)
from benchmarks.lib.q3_programs import (JOIN_GROUPBY_LABELS,  # noqa: E402
                                        TOPN_LABELS)

CELL = "q3_join_groupby_topn"


@pytest.fixture(scope="module")
def config():
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(CELL)["config"]))
    return cfg, m.config_module(cfg, "reference"), m.config_module(cfg, "query")


# -- the manifest --------------------------------------------------------------

REPORTS = ["ingest_dispatches", "ingest_stall_ms", "concat_busy_share",
           "stage_dispatches", "compile_s", "window_compiles",
           "query_hbm_share", "device_idle_share", "hbm_peak_gib",
           "plan_span_ms", "scan_decode_ms", "upload_ms", "device_wait_ms",
           "ingest_busy_share", "labelled_busy_share", "direct_pack_share",
           "join_busy_share", "join_build_ms", "decode_busy_share",
           "groupby_ms", "sort_ms", "groupby_fallbacks"]
ITS_OWN = ["plan_reruns", "join_groupby_roofline", "topn_busy_share"]
# nothing of it runs in this cell: the fused join stage's sizing program and
# roofline (the SF1 plan joins per operator, behind an adaptive join), the
# other cells' kernels, string group keys
NOT_THE_CELLS = ["agg_stage_roofline", "join_probe_roofline",
                 "join_sizing_dispatches", "groupby_busy_share",
                 "groupby_update_roofline", "groupby_lane_share"]


def test_the_manifest_names_the_cell_and_its_configuration():
    doc = Manifest().doc
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch-q3", "closed1", 1)
    config = {c["name"]: c for c in doc["configs"]}["tpch-q3"]
    assert config["file"] == "benchmarks/configs/tpch-q3/config.json"
    assert "clause 2.4.3 Q3" in config["source"] and not config["reduced"]


@pytest.mark.parametrize("metric", REPORTS + ITS_OWN + NOT_THE_CELLS)
def test_the_cell_is_on_the_list_of_every_metric_it_reports(metric):
    entry = {e["name"]: e for e in Manifest().doc["per_layer"]}[metric]
    if metric in NOT_THE_CELLS:
        assert CELL not in entry["workloads"]
    elif metric in ITS_OWN:
        assert entry["workloads"] == [CELL] and entry["moves"] == "query_s"
    else:
        assert CELL in entry["workloads"]
        assert entry["workloads"][0] != CELL        # an older cell's metric


# -- the generator -------------------------------------------------------------

@pytest.mark.parametrize("seed", [5, 6])
def test_orders_customers_and_lines_keep_the_specifications_rules(seed):
    """Clause 4.2.3: O_ORDERKEY the first 8 of every 32; no O_CUSTKEY
    divisible by 3, every one a customer's; L_ORDERKEY its order's, 1 to 7
    lines an order; L_SHIPDATE 1 to 121 days after its order's date;
    O_SHIPPRIORITY 0; five segments, about a fifth each; the same number of
    rows for every seed."""
    rng = np.random.default_rng(seed)
    cust = dbgen_q3.customers(rng, 1500)
    orders, line = dbgen_q3.orders_and_lineitems(rng, 60012, 1500, 0.01)
    assert cust["c_custkey"].tolist() == list(range(1, 1501))
    assert set(cust["c_mktsegment"]) == set(dbgen_q3.SEGMENTS)
    assert isinstance(cust["c_mktsegment"][0], str)
    share = np.mean(cust["c_mktsegment"] == "BUILDING")
    assert 0.15 < share < 0.25
    okey = orders["o_orderkey"]
    assert len(okey) == 15000 and (np.diff(okey) > 0).all()
    assert ((okey - 1) % 32 < 8).all() and okey[:9].tolist() == \
        [1, 2, 3, 4, 5, 6, 7, 8, 33]
    ckey = orders["o_custkey"]
    assert (ckey % 3 != 0).all() and ckey.min() >= 1 and ckey.max() <= 1500
    assert len(set(ckey.tolist())) > 900          # spread over the 1,000
    assert not orders["o_shippriority"].any()
    assert orders["o_orderdate"].min() >= dbgen.STARTDATE
    assert orders["o_orderdate"].max() <= dbgen.ENDDATE - 151
    assert len(line["l_orderkey"]) == 60012
    keys, per_order = np.unique(line["l_orderkey"], return_counts=True)
    assert set(keys.tolist()) <= set(okey.tolist())
    assert per_order.min() >= 1 and per_order.max() <= 7
    at = np.searchsorted(okey, line["l_orderkey"])
    late = line["l_shipdate"] - orders["o_orderdate"][at]
    assert late.min() >= 1 and late.max() <= 121
    unit = line["l_extendedprice"] / line["l_quantity"]
    assert 900.0 <= unit.min() and unit.max() <= 2100.0


def test_generate_gives_the_configurations_tables_and_types(config, tmp_path):
    import pyarrow.parquet as pq
    cfg, ref, _ = config
    tables = ref.generate(11, cfg)
    assert {t: list(cols) for t, cols in tables.items()} == \
        {t: list(cols) for t, cols in cfg["schema"].items()}
    again = ref.generate(11, cfg)                 # the seed alone
    assert all((tables[t][c] == again[t][c]).all()
               for t in tables for c in tables[t])
    paths = datagen.write_tables(str(tmp_path), tables, cfg["schema"],
                                 cfg["layout"])
    seg = pq.ParquetFile(paths["customer"].replace("*", "part-000"))
    col = seg.metadata.row_group(0).column(1)
    assert "DICTIONARY" in str(col.encodings)     # as Parquet writes it
    types = {f.name: str(f.type) for f in pq.read_schema(
        paths["orders"].replace("*", "part-000"))}
    assert types == {"o_orderkey": "int64", "o_custkey": "int64",
                     "o_orderdate": "date32[day]", "o_shippriority": "int32"}


# -- the query against the reference -------------------------------------------

def _session_and_paths(cfg, ref, seed, tmp_path):
    from spark_rapids_tpu.api.session import TpuSession
    tables = ref.generate(seed, cfg)
    paths = datagen.write_tables(str(tmp_path / str(seed)), tables,
                                 cfg["schema"], cfg["layout"])
    return TpuSession(dict(cfg["session_conf"])), tables, paths


def _labels():
    from spark_rapids_tpu.obs import dispatch
    out = {}
    for p in dispatch.programs():
        out[p["label"]] = out.get(p["label"], 0) + p["dispatches"]
    return out


@pytest.mark.parametrize("seed", [3000000019, 7])
def test_q3_through_the_session_is_the_references_ten_rows_in_one_pass(
        config, seed, tmp_path):
    """The rehearsal plans what SF1 plans (an adaptive join over the join of
    orders and customer); there are more groups than the 64 masked slots,
    so the FIRST query runs its plan twice and every later one once: the
    exact step alone, each join's build once, no trace."""
    from spark_rapids_tpu.exec import aggregate, stage_compiler
    from spark_rapids_tpu.obs import dispatch
    cfg, ref, query = config
    stage_compiler.reset_stage_counters()
    sess, tables, paths = _session_and_paths(cfg, ref, seed, tmp_path)
    answer = ref.reference(tables, cfg)
    assert answer["groups"] > 64 and len(answer["rows"]) == 11
    df = query.build(sess, paths, cfg)
    assert "AdaptiveJoinExec" in df._exec().tree_string()
    for n in range(3):
        before, c0, t0 = _labels(), aggregate.counters(), \
            dispatch.counters()["traces"]
        rows = query.build(sess, paths, cfg).collect()
        after, c1 = _labels(), aggregate.counters()
        got = ref.compare(rows, answer)
        assert got["rows_wrong"] == 0 and got["near_ties"] == 0, (rows, answer)
        assert got["sum_rel_err"] <= cfg["limits"]["sum_rel_err"]
        ran = {k: after[k] - before.get(k, 0) for k in after}
        moved = {k: c1[k] - c0[k] for k in c1 if c1[k] != c0[k]}
        if n == 0:
            assert moved["plan_reruns"] == moved["spec_trips"] == 1
            assert ran["CompiledStageExec.step"] == 1
            assert ran["HashJoinExec.build"] == 4
        else:
            assert moved == {"executions": 1, "many_group_updates": 1}
            assert ran["CompiledStageExec.step"] == 0
            assert ran["CompiledStageExec.step_exact"] == 1
            assert ran["HashJoinExec.build"] == 2     # one a join
            assert ran["SortExec.sort"] == 1
            assert dispatch.counters()["traces"] == t0


def test_the_float32_control_fails_the_revenues_limit_alone(config):
    cfg, ref, _ = config
    for seed in (3000000019, 11, 12):
        tables = ref.generate(seed, cfg)
        answer = ref.reference(tables, cfg)
        low = ref.as_rows(ref.reference(tables, cfg, np.float32))
        got = ref.compare(low, answer)
        assert got["sum_rel_err"] > 30 * cfg["limits"]["sum_rel_err"]
        assert ref.compare(ref.as_rows(answer), answer) == \
            {"rows_wrong": 0, "near_ties": 0, "sum_rel_err": 0.0}


# -- the comparison ------------------------------------------------------------

def _answer(revenues, near=1e-10):
    rows = [(100 + i, r, 9000 + i, 0) for i, r in enumerate(revenues)]
    return {"rows": rows, "near": near}


FAR = [500.0 - 10 * i for i in range(11)]
TIED_23 = FAR[:2] + [480.0 * (1 + 4e-11)] + [480.0] + FAR[4:]
TIED_1011 = FAR[:9] + [410.0, 410.0 * (1 - 1e-12)]


def _swap(rows, i, j):
    rows = list(rows)
    rows[i], rows[j] = rows[j], rows[i]
    return rows


@pytest.mark.parametrize("revenues,answer_rows,wrong,near_ties", [
    (FAR, lambda a: a["rows"][:10], 0, 0),
    # two neighbours farther apart than the limit, swapped: both wrong
    (FAR, lambda a: _swap(a["rows"][:10], 2, 3), 2, 0),
    # within the limit of each other: either order is right, and said so
    (TIED_23, lambda a: a["rows"][:10], 0, 2),
    (TIED_23, lambda a: _swap(a["rows"][:10], 2, 3), 0, 2),
    # ... but not a row of the run twice, nor one from outside it
    (TIED_23, lambda a: a["rows"][:2] + [a["rows"][2]] * 2 + a["rows"][4:10],
     1, 2),
    (TIED_23, lambda a: _swap(a["rows"][:10], 1, 2), 2, 2),
    # a tied 10th and 11th: either of them closes the answer
    (TIED_1011, lambda a: a["rows"][:10], 0, 1),
    (TIED_1011, lambda a: a["rows"][:9] + [a["rows"][10]], 0, 1),
    (FAR, lambda a: a["rows"][:9] + [a["rows"][10]], 1, 0),
    # a row too few, a row too many, a NULL, a key that differs
    (FAR, lambda a: a["rows"][:9], 1, 0),
    (FAR, lambda a: a["rows"][:11], 1, 0),
    (FAR, lambda a: [(100, None, 9000, 0)] + a["rows"][1:10], 1, 0),
    (FAR, lambda a: [(100, 500.0, 9001, 0)] + a["rows"][1:10], 1, 0),
], ids=["same", "swapped_far", "tied_same", "tied_swapped", "tied_twice",
        "tied_outside", "tenth_same", "tenth_is_eleventh",
        "eleventh_untied", "short", "long", "null", "date_differs"])
def test_compare_by_position_and_its_rule_for_tied_revenues(
        config, revenues, answer_rows, wrong, near_ties):
    _, ref, _ = config
    answer = _answer(revenues)
    got = ref.compare(answer_rows(answer), answer)
    assert (got["rows_wrong"], got["near_ties"]) == (wrong, near_ties)


def test_compare_reads_the_revenues_error_and_the_smallest_gap(config):
    _, ref, _ = config
    answer = _answer(FAR)
    rows = [(k, r * (1 + 1e-9), d, p) if k == 103 else (k, r, d, p)
            for k, r, d, p in answer["rows"][:10]]
    assert ref.compare(rows, answer)["sum_rel_err"] == \
        pytest.approx(1e-9, rel=1e-3)
    rows[5] = rows[5][:1] + (float("nan"),) + rows[5][2:]
    assert ref.compare(rows, answer)["sum_rel_err"] == float("inf")
    assert ref.smallest_gap(answer) == pytest.approx(10 / 500.0)
    assert ref.smallest_gap(_answer(TIED_23)) == pytest.approx(4e-11,
                                                               rel=1e-3)
    assert ref.smallest_gap(_answer([1.0])) == float("inf")


def test_the_work_model_counts_the_three_tables_once(config):
    cfg, ref, _ = config
    tables = ref.generate(7, cfg)
    strings = sum(len(s) for s in tables["customer"]["c_mktsegment"])
    scale = cfg["scale"]
    assert ref.work_model(cfg, tables) == {"join_groupby": {
        "bytes": scale["lineitem_rows"] * 28 + scale["orders_rows"] * 24
        + scale["customer_rows"] * 8 + strings + 10 * 24,
        "bound": "memory"}}


# -- the three readers ---------------------------------------------------------

class Obs:
    """A hand-made observation: what `lib/observe.Observation` carries."""

    def __init__(self, queries=2, families=None, trace=None, work=None):
        self.queries = queries
        self.window = {"families": families or {}, "phases": {},
                       "labels": {}}
        self.trace = trace
        self.work = work or {}
        self.peaks = peaks.peaks_for("TPU v5 lite")


def _reduced(module_s, busy_s=4.0):
    return trace.Reduced(1, 5.0, busy_s, dict(module_s),
                         {m: 2 for m in module_s}, [], [])


MODULES = {"jit__agg_exact_body": ["CompiledStageExec.step_exact"],
           "jit__probe_kernel": ["HashJoinExec.probe"],
           "jit__counts_kernel": ["HashJoinExec.counts"],
           "jit__build_kernel": ["HashJoinExec.build"],
           "jit__sort_kernel": ["SortExec.sort"],
           "jit__concat_pair": ["coalesce.concat_pair"]}


@pytest.fixture
def module_labels(monkeypatch):
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


@pytest.mark.parametrize("families,queries,want", [
    ({"aggregate": {"plan_reruns": 0, "executions": 4}}, 4, 0.0),
    ({"aggregate": {"plan_reruns": 4, "executions": 8}}, 4, 1.0),
    ({"aggregate": {"executions": 4}}, 4, None),      # the parent's counters
    ({}, 4, None),
    ({"aggregate": {"plan_reruns": 0}}, 0, None),
])
def test_plan_reruns_reads_the_windows_counter(families, queries, want):
    read = Manifest().reader("plan_reruns")
    assert read(Obs(queries, families)) == want


def test_the_two_trace_readers_find_their_programs_by_label(module_labels):
    m = Manifest()
    roofline, topn = m.reader("join_groupby_roofline"), \
        m.reader("topn_busy_share")
    assert "HashJoinExec.probe" in JOIN_GROUPBY_LABELS
    assert "SortExec.sort" in TOPN_LABELS
    work = {"join_groupby": {"bytes": 819e6, "bound": "memory"}}   # 1 ms
    red = _reduced({"jit__probe_kernel": 1.0, "jit__counts_kernel": 0.5,
                    "jit__build_kernel": 0.5, "jit__sort_kernel": 0.02,
                    "jit__concat_pair": 1.0, "jit__agg_exact_body": 0.5})
    module_labels(MODULES)
    # the stage's exact step is the agg stage's, not a join label: the
    # roofline counts the three join programs
    assert roofline(Obs(trace=red, work=work)) == pytest.approx(
        100 * 2 * 1e-3 / 2.0)
    assert topn(Obs(trace=red, work=work)) == pytest.approx(100 * 0.02 / 4.0)
    for read in (roofline, topn):
        assert read(Obs(trace=None, work=work)) is None
        assert read(Obs(trace=_reduced({"jit__concat_pair": 1.0}),
                        work=work)) is None           # silent, never 0
    assert roofline(Obs(trace=red, work={"join_probe": {"bytes": 1}})) is None
    module_labels({})                                 # a program without the map
    assert roofline(Obs(trace=red, work=work)) is None
    assert topn(Obs(trace=red, work=work)) is None


# -- the refusal ---------------------------------------------------------------

def test_a_plan_without_a_fingerprint_is_refused_before_the_first_scan(
        config, tmp_path, monkeypatch):
    """The parent of PR 38: `AdaptiveJoinExec` opted out of the plan
    fingerprint, so every `collect()` compiled the plan's programs anew.
    Refused from the plan alone, once a process, with the plan in the
    words."""
    from spark_rapids_tpu.exec.joins import AdaptiveJoinExec
    from spark_rapids_tpu.obs import dispatch
    cfg, ref, query = config
    sess, _, paths = _session_and_paths(cfg, ref, 5, tmp_path)
    monkeypatch.setattr(query, "_plan_checked", False)
    monkeypatch.setattr(AdaptiveJoinExec, "_fingerprint_extras",
                        lambda self: None)
    before = dispatch.counters()["dispatches"]
    with pytest.raises(RuntimeError, match="without a plan fingerprint") as e:
        query.build(sess, paths, cfg)
    assert "AdaptiveJoinExec" in str(e.value)
    assert dispatch.counters()["dispatches"] == before    # nothing ran
    monkeypatch.undo()
    monkeypatch.setattr(query, "_plan_checked", False)
    query.build(sess, paths, cfg)                          # the change passes
    assert query._plan_checked is True


# -- the traced rehearsal ------------------------------------------------------

def test_the_traced_rehearsal_reports_the_spans_and_the_counters(tmp_path):
    from benchmarks.lib import harness
    from spark_rapids_tpu.exec import stage_compiler
    stage_compiler.reset_stage_counters()     # a process that never saw Q3
    res = harness.run_cell(CELL, 2147483659, 0.5, True, require_tpu=False,
                           rehearse=True, manifest=manifest_at(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["plan_reruns"] == 0 and got["groupby_fallbacks"] == 0
    assert got["groupby_ms"] > 0 and got["sort_ms"] > 0
    assert got["join_build_ms"] > 0
    assert got["window_compiles"] == 0
    window, setup = res["families"]["window"]["aggregate"], \
        res["families"]["setup"]["aggregate"]
    assert (setup["plan_reruns"], setup["spec_trips"]) == (1, 1)
    assert window["executions"] == window["many_group_updates"] == 4
    assert window["plan_reruns"] == window["spec_trips"] == 0
