"""CPU tests of what the configuration `tpch-q1` and its cell
`q1_groupby_string_keys` add (ISSUE 35): the generator of the lineitem
columns Q1 reads, the session path against the plain reference at the
rehearsal size, the per-layer readers, and the check that refuses a
program whose result sort the chip's compiler cannot finish. The
cell-parametrised tests of `test_benchmark_harness.py` pick the cell up from
`BENCHMARK.json` by themselves (sound run, traced run, four faults, float32
control, work model). None of this is a chip run."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import datagen, dbgen, dbgen_q1, peaks, trace  # noqa: E402
from benchmarks.lib.groupby_programs import (  # noqa: E402
    GROUPBY_LABELS, UPDATE_LABELS)
from benchmarks.lib.manifest import (Manifest, apply_rehearsal,  # noqa: E402
                                     manifest_at)
from appending import lists_the_cell  # noqa: E402

CELL = "q1_groupby_string_keys"
UPDATE, PRE, EVAL = "jit__traced", "jit__pre_project", "jit__evaluate"
SORT, DECODE, CONCAT = "jit__sort_kernel", "jit__decode", "jit__concat_pair"
LABELS = {UPDATE: ["AggregateExec.update_hash"],
          PRE: ["AggregateExec.pre_project"],
          EVAL: ["AggregateExec.evaluate"],
          "jit__shrink_batch": ["aggregate.shrink_batch"],
          SORT: ["SortExec.sort"],
          "jit__max_string_length": ["sort.key_width"],
          "jit__map_body": ["CompiledStageExec.map"],
          DECODE: ["encoded.decode"],
          CONCAT: ["coalesce.concat_pair"]}
FOUR_GROUPS = [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]


@pytest.fixture(scope="module")
def config():
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(CELL)["config"]))
    return cfg, m.config_module(cfg, "reference"), m.config_module(cfg, "query")


# -- the manifest --------------------------------------------------------------

# The per-layer metrics by what this cell has to do with them. Nothing here
# says where a list ends: a later cell is appended after this one
# (`test_manifest_append_only.py` holds what was accepted in its place).
REPORTS = ["ingest_dispatches", "ingest_stall_ms", "concat_busy_share",
           "stage_dispatches", "compile_s", "window_compiles",
           "query_hbm_share", "device_idle_share", "hbm_peak_gib",
           "plan_span_ms", "scan_decode_ms", "upload_ms", "device_wait_ms",
           "ingest_busy_share", "labelled_busy_share", "decode_busy_share",
           "direct_pack_share"]
ITS_OWN = ["groupby_busy_share", "groupby_update_roofline", "groupby_ms",
           "sort_ms", "groupby_fallbacks", "groupby_lane_share"]
NOT_THE_CELLS = ["agg_stage_roofline", "join_busy_share",
                 "join_probe_roofline", "join_build_ms",
                 "join_sizing_dispatches"]


def test_the_manifest_names_the_cell_and_its_configuration(doc):
    cell = {w["name"]: w for w in doc["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch-q1", "closed1", 1)
    config = {c["name"]: c for c in doc["configs"]}["tpch-q1"]
    assert config["file"] == "benchmarks/configs/tpch-q1/config.json"
    assert "clause 2.4.1 Q1" in config["source"] and not config["reduced"]


@pytest.mark.parametrize("metric", REPORTS + ITS_OWN + NOT_THE_CELLS)
def test_the_cell_is_appended_to_the_lists_of_the_metrics_it_reports(doc, metric):
    lists_the_cell(doc, CELL, metric, ITS_OWN, NOT_THE_CELLS)


# -- the generator -------------------------------------------------------------

def test_the_flags_and_the_tax_keep_the_specifications_rules():
    """Clause 4.2.3: L_TAX in hundredths up to 0.08; L_RECEIPTDATE within 30
    days after L_SHIPDATE; L_RETURNFLAG "N" exactly where the receipt date is
    after CURRENTDATE (1995-06-17), else "R" or "A", about half each;
    L_LINESTATUS "O" exactly where the ship date is after CURRENTDATE; the
    columns `dbgen.py` makes keep its rules; the same number of rows for
    every seed."""
    epoch = np.datetime64("1970-01-01")
    assert dbgen_q1.CURRENTDATE == \
        (np.datetime64("1995-06-17") - epoch).astype(int)
    sizes = set()
    for seed in (5, 6):
        li = dbgen_q1.lineitems_with_flags(np.random.default_rng(seed),
                                           60012, 0.01)
        sizes.add(tuple(sorted(len(v) for v in li.values())))
        assert set(np.round(li["l_tax"] * 100).astype(int)) == set(range(9))
        assert set(np.round(li["l_discount"] * 100).astype(int)) == \
            set(range(11))
        late = li["l_receiptdate"] - li["l_shipdate"]
        assert late.min() == 1 and late.max() == 30
        flag, status = li["l_returnflag"], li["l_linestatus"]
        assert flag.dtype == object and isinstance(flag[0], str)
        received = li["l_receiptdate"] <= dbgen_q1.CURRENTDATE
        assert ((flag == "N") == ~received).all()
        assert set(flag[received]) == {"R", "A"}
        assert 0.45 < np.mean(flag[received] == "R") < 0.55
        assert ((status == "O") == (li["l_shipdate"] >
                                    dbgen_q1.CURRENTDATE)).all()
        assert set(status) == {"O", "F"}
        assert set(zip(flag, status)) == set(FOUR_GROUPS)
        assert li["l_shipdate"].min() >= dbgen.STARTDATE + 1
        assert li["l_shipdate"].max() <= dbgen.ENDDATE - 151 + 121
        unit = li["l_extendedprice"] / li["l_quantity"]
        assert 900.0 <= unit.min() and unit.max() <= 2100.0
    assert sizes == {(60012,) * 8}


def test_generate_gives_the_configurations_table_and_types(config, tmp_path):
    cfg, ref, _ = config
    tables = ref.generate(11, cfg)
    assert list(tables) == ["lineitem"]
    assert list(tables["lineitem"]) == list(cfg["schema"]["lineitem"])
    assert len(tables["lineitem"]["l_tax"]) == cfg["scale"]["lineitem_rows"]
    same = ref.generate(11, cfg)
    assert all((tables["lineitem"][c] == same["lineitem"][c]).all()
               for c in tables["lineitem"])
    other = ref.generate(12, cfg)
    assert len(other["lineitem"]["l_tax"]) == cfg["scale"]["lineitem_rows"]
    # the two flags reach Parquet as string columns (`lib/datagen.py` gives
    # pyarrow no type for them), dictionary-encoded as Parquet writes them
    import pyarrow as pa
    import pyarrow.parquet as pq
    paths = datagen.write_tables(str(tmp_path), tables, cfg["schema"],
                                 cfg["layout"])
    files = sorted(Path(paths["lineitem"]).parent.glob("*.parquet"))
    assert len(files) == cfg["layout"]["lineitem"]["files"]
    schema = pq.read_schema(files[0])
    assert schema.field("l_returnflag").type == pa.string()
    assert schema.field("l_linestatus").type == pa.string()
    assert schema.field("l_shipdate").type == pa.date32()
    meta = pq.ParquetFile(files[0]).metadata
    assert meta.num_row_groups == \
        cfg["layout"]["lineitem"]["row_groups_per_file"]
    flag = meta.row_group(0).column(list(schema.names).index("l_returnflag"))
    assert any("DICTIONARY" in e for e in flag.encodings)
    assert ref.cutoff(cfg) == 10471                    # 1998-09-02


# -- the session path against the plain reference ------------------------------

def _collect(cfg, query, tables, where):
    from spark_rapids_tpu.api.session import TpuSession
    paths = datagen.write_tables(str(where), tables, cfg["schema"],
                                 cfg["layout"])
    return query.build(TpuSession(dict(cfg["session_conf"])), paths,
                       cfg).collect()


def _a_null_in_each_key(tables, cfg):
    """Some rows without a return flag, some without a line status, a few
    without either: NULL is a group key of its own, and sorts first."""
    line = dict(tables["lineitem"])
    n = len(line["l_shipdate"])
    for k, (start, step) in {"l_returnflag": (0, 7),
                             "l_linestatus": (3, 10)}.items():
        col = line[k].copy()
        col[start:n:step] = None
        line[k] = col
    return {"lineitem": line}, cfg


def _no_row_kept(tables, cfg):
    """A cut-off before the first ship date: zero groups, zero rows."""
    return tables, {**cfg, "params": {"delta_days": 4000}}


@pytest.mark.parametrize("seed,alter,groups", [
    (3000000019, None, FOUR_GROUPS),
    (2147483659, None, FOUR_GROUPS),        # past 32 signed bits
    (17, None, FOUR_GROUPS),
    (17, _a_null_in_each_key, "nulls"),
    (17, _no_row_kept, []),
], ids=["seed_a", "seed_b", "seed_c", "null_keys", "no_row_kept"])
def test_the_session_path_equals_the_plain_reference(config, tmp_path, seed,
                                                     alter, groups):
    cfg, ref, query = config
    tables = ref.generate(seed, cfg)
    if alter is not None:
        tables, cfg = alter(tables, cfg)
    answer = ref.reference(tables, cfg)
    keys = [r[:2] for r in answer]
    if groups == "nulls":
        # Spark's ascending order: NULL before every value, key by key
        assert keys[0] == (None, None) and keys[1][0] is None
        assert [k for k in keys if None not in k] == FOUR_GROUPS
        assert len(keys) == 3 + 3 + 4
    else:
        assert keys == groups
    if answer:
        kept = tables["lineitem"]["l_shipdate"] <= ref.cutoff(cfg)
        assert sum(r[-1] for r in answer) == kept.sum()
        assert 0.97 < kept.mean() < 0.995                  # about 98% kept
    rows = _collect(cfg, query, tables, tmp_path)
    got = ref.compare(rows, answer)
    assert got["rows_wrong"] == 0, (rows, answer)
    assert got["sum_rel_err"] <= cfg["limits"]["sum_rel_err"], (rows, answer)
    assert [r[:2] for r in rows] == keys


def test_the_float32_control_fails_sum_rel_err_alone(config):
    cfg, ref, _ = config
    for seed in (3, 4, 5):
        tables = ref.generate(seed, cfg)
        answer = ref.reference(tables, cfg)
        low = ref.compare(ref.as_rows(ref.reference(tables, cfg, np.float32)),
                          answer)
        assert low["rows_wrong"] == 0
        assert low["sum_rel_err"] > 10 * cfg["limits"]["sum_rel_err"]
    assert cfg["limits"]["sum_rel_err"] < 1e-9          # the harness's nudge
    assert cfg["limits"]["rows_wrong"] == 0


def test_the_scatter_adds_float32_pair_bias_would_fail_the_limit(config):
    """Until PR 36 the program read 1.22e-10..1.25e-10 on every seed: ONE
    column, `avg_disc`, whose float32-pair scatter-add rounded the same way
    add after add. The limit sits a decade under that bias, so the bias fails
    if it comes back, and at least four times over what the program reads on
    the chip since (2.5e-14 at most: `PERF.md` section 2)."""
    cfg, ref, _ = config
    limit = cfg["limits"]["sum_rel_err"]
    assert 4 * 2.5e-14 <= limit <= 1.22e-10 / 10
    tables = ref.generate(3, cfg)
    answer = ref.reference(tables, cfg)
    biased = [tuple(v * (1 + 1.22e-10) if i == 8 else v      # avg_disc
                    for i, v in enumerate(row)) for row in ref.as_rows(answer)]
    got = ref.compare(biased, answer)
    assert got["rows_wrong"] == 0
    assert 1.2e-10 < got["sum_rel_err"] < 1.25e-10 and \
        got["sum_rel_err"] > 10 * limit


def test_compare_counts_rows_keys_counts_nulls_and_values_not_finite(config):
    _, ref, _ = config
    answer = [("A", "F", 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 10),
              ("N", "O", 1.5, 2.5, 3.5, 4.5, 5.5, 6.5, 7.5, 20)]
    sound = {"rows_wrong": 0, "sum_rel_err": 0.0}
    assert ref.compare(list(answer), answer) == sound
    assert ref.compare([], []) == sound

    def altered(row, col, value):
        rows = [list(r) for r in answer]
        rows[row][col] = value
        return [tuple(r) for r in rows]

    assert ref.compare(answer[:1], answer)["rows_wrong"] == 1   # a row lost
    assert ref.compare(answer + answer[:1], answer)["rows_wrong"] == 1
    assert ref.compare(answer[::-1], answer)["rows_wrong"] == 2  # positions
    assert ref.compare(altered(1, 0, "R"), answer)["rows_wrong"] == 1
    assert ref.compare(altered(1, 1, None), answer)["rows_wrong"] == 1
    assert ref.compare(altered(0, 9, 11), answer)["rows_wrong"] == 1
    assert ref.compare(altered(0, 4, None), answer)["rows_wrong"] == 1
    # the LARGEST error over the seven DOUBLE columns of every row
    got = ref.compare(altered(1, 8, 7.5 * (1 + 1e-9)), answer)
    assert got["rows_wrong"] == 0
    assert got["sum_rel_err"] == pytest.approx(1e-9, rel=1e-3)
    got = ref.compare(altered(0, 2, 1.0 * (1 + 1e-9)), answer)
    assert got["sum_rel_err"] == pytest.approx(1e-9, rel=1e-3)
    assert ref.compare(altered(1, 5, float("nan")), answer)["sum_rel_err"] \
        == float("inf")
    assert ref.compare(altered(1, 5, float("inf")), answer)["sum_rel_err"] \
        == float("inf")


def test_the_work_model_counts_the_columns_as_stored_and_the_result(config):
    cfg, ref, _ = config
    tables = ref.generate(7, cfg)
    work = ref.work_model(cfg, tables)
    rows = cfg["scale"]["lineitem_rows"]
    assert work == {"groupby_update": {
        "bytes": rows * (4 * 8 + 4 + 1 + 1) + 4 * (2 + 7 * 8 + 8),
        "bound": "memory"}}
    full = Manifest().config("tpch-q1")["scale"]["lineitem_rows"]
    assert full * 38 + 4 * 66 == 228_046_434            # 228.0 MB a query


# -- the check that refuses a program whose sort cannot be compiled ------------

def test_the_result_sort_check_passes_here_and_refuses_the_parents_lanes(
        config, monkeypatch):
    """`query.py` asks the PROGRAM how many key lanes it sorts Q1's two
    one-byte keys on. This tree: one packed lane and the iota. The parent of
    ISSUE 35 (commit 0135df4): 20, recorded from a run of this check on it;
    the chip's compiler did not finish that sort in 38 minutes, and a parent
    that hangs in the new cell refuses the PR."""
    _, _, query = config
    assert query.result_sort_keys() == 2 <= query.MAX_SORT_KEYS
    monkeypatch.setattr(query, "_sort_keys_checked", False)
    query.check_result_sort()                            # passes, once
    assert query._sort_keys_checked is True
    monkeypatch.setattr(query, "result_sort_keys", lambda: 1 / 0)
    query.check_result_sort()                            # not asked again
    monkeypatch.setattr(query, "_sort_keys_checked", False)
    monkeypatch.setattr(query, "result_sort_keys", lambda: 20)
    with pytest.raises(RuntimeError, match="on 20 key lanes"):
        query.check_result_sort()
    assert query._sort_keys_checked is False             # and again next time
    with pytest.raises(RuntimeError, match="on 20 key lanes"):
        query.build(None, {}, {})                        # before any scan


# -- the readers ---------------------------------------------------------------

class Obs:
    """A hand-made observation: what `lib/observe.Observation` carries."""

    def __init__(self, queries=2, phases=None, trace=None, work=None):
        self.queries = queries
        self.window = {"phases": phases or {}, "labels": {}}
        self.trace = trace
        self.work = work or {}
        self.peaks = peaks.peaks_for("TPU v5 lite")


def _reduced(module_s, busy_s=4.0):
    return trace.Reduced(1, 5.0, busy_s, dict(module_s),
                         {m: 2 for m in module_s}, [], [])


@pytest.fixture
def module_labels(monkeypatch):
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


def test_the_groupby_readers_find_its_programs_by_their_labels(module_labels):
    m = Manifest()
    share = m.reader("groupby_busy_share")
    roofline = m.reader("groupby_update_roofline")
    work = {"groupby_update": {"bytes": 819e6, "bound": "memory"}}   # 1 ms
    red = _reduced({UPDATE: 1.2, PRE: 0.4, EVAL: 0.1, "jit__shrink_batch": 0.1,
                    SORT: 0.2, DECODE: 1.0, CONCAT: 0.5, "jit_gather": 0.5})
    module_labels(LABELS)
    assert share(Obs(trace=red, work=work)) == pytest.approx(100 * 1.8 / 4.0)
    # the roofline takes the programs that see every source row, and not
    # the evaluation or the shrink of the four-row result
    assert roofline(Obs(trace=red, work=work)) == pytest.approx(
        100 * 2 * 1e-3 / 1.6)
    # another cell's work model: the roofline has nothing to divide
    assert roofline(Obs(trace=red, work={"agg_stage": {"bytes": 1}})) is None
    assert UPDATE_LABELS < GROUPBY_LABELS
    assert not any(label.startswith(("SortExec", "encoded", "sort."))
                   for label in GROUPBY_LABELS)


def test_the_groupby_readers_are_silent_where_nothing_can_be_read(
        module_labels, monkeypatch):
    m = Manifest()
    work = {"groupby_update": {"bytes": 819e6, "bound": "memory"}}
    red = _reduced({UPDATE: 1.2, CONCAT: 0.5})
    for name in ("groupby_busy_share", "groupby_update_roofline"):
        read = m.reader(name)
        module_labels(LABELS)
        assert read(Obs(trace=None, work=work)) is None       # no trace
        assert read(Obs(trace=_reduced({CONCAT: 0.5}), work=work)) is None
        module_labels({})                                     # ledger off
        assert read(Obs(trace=red, work=work)) is None        # never 0
        # one module serving the group-by and something else: cannot be split
        module_labels({**LABELS, UPDATE: ["AggregateExec.update_hash",
                                          "HashJoinExec.probe"]})
        assert read(Obs(trace=red, work=work)) is None
        from spark_rapids_tpu.obs import dispatch
        monkeypatch.delattr(dispatch, "module_labels")        # before PR 27
        assert read(Obs(trace=red, work=work)) is None
        monkeypatch.undo()


@pytest.mark.parametrize("metric,phase", [("groupby_ms", "group-agg"),
                                          ("sort_ms", "sort")])
def test_the_span_readers_read_their_phase_and_are_silent_without_it(
        metric, phase):
    read = Manifest().reader(metric)
    assert read(Obs(2, {phase: 13_000_000, "plan": 5})) == pytest.approx(6.5)
    assert read(Obs(2, {phase: 0})) == 0.0            # a time of nothing
    assert read(Obs(2, {"plan": 5})) is None          # the parent's program
    assert read(Obs(0, {phase: 13_000_000})) is None


def test_groupby_fallbacks_reads_the_windows_counters():
    read = Manifest().reader("groupby_fallbacks")

    def obs(**c):
        o = Obs()
        o.window["families"] = {"aggregate": c}
        return o

    assert read(obs(executions=6, hash_updates=6, hash_round_retries=0,
                    exact_fallbacks=0)) == 0.0        # a count: 0 is a reading
    assert read(obs(executions=6, hash_updates=6, hash_round_retries=6,
                    exact_fallbacks=3)) == 1.5
    assert read(obs(executions=0, hash_updates=0, hash_round_retries=0,
                    exact_fallbacks=0)) is None       # no group-by drove
    assert read(obs(hash_updates=1)) is None          # other counters
    assert read(obs()) is None                        # the parent's program
    assert read(Obs()) is None                        # no families carried


def test_the_traced_rehearsal_reports_the_groupbys_spans_and_counters(
        tmp_path):
    from benchmarks.lib import harness
    from spark_rapids_tpu.exec import aggregate
    from spark_rapids_tpu.obs import dispatch
    # a root of its own: the cell-parametrised tests rehearse this cell in
    # the checkout's `.bench_work/<cell>` from another worker
    own = manifest_at(tmp_path)
    before = aggregate.counters()
    res = harness.run_cell(CELL, 2147483659, 0.5, True, require_tpu=False,
                           rehearse=True, manifest=own)
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert got["groupby_ms"]["unit"] == got["sort_ms"]["unit"] == "ms/query"
    assert got["groupby_ms"]["value"] > 0 and got["sort_ms"]["value"] > 0
    # four groups resolve in the hash update's first two rounds
    assert got["groupby_fallbacks"] == {"value": 0.0, "unit": "count/query"}
    # every update rode the lane tier; five of the seven columns of a batch
    # (four DOUBLE, the DATE) are packed straight from their Arrow buffers
    assert got["groupby_lane_share"] == {"value": 100.0, "unit": "%"}
    assert got["direct_pack_share"]["value"] == pytest.approx(100 * 5 / 7)
    window = res["families"]["window"]["aggregate"]
    assert window["executions"] == window["lane_updates"] == res["attempted"]
    after = aggregate.counters()
    queries = after["executions"] - before["executions"]
    assert queries >= res["attempted"] + 1            # the warm-up's too
    assert after["hash_updates"] - before["hash_updates"] == queries
    # on the CPU there is no device plane: the two trace-fed ones are silent
    assert "groupby_busy_share" not in got
    assert "groupby_update_roofline" not in got
    # every program of the group-by and the sort ran under a ledger label
    served = {label for labels in dispatch.module_labels().values()
              for label in labels}
    assert {"AggregateExec.pre_project", "AggregateExec.update_hash",
            "AggregateExec.evaluate", "SortExec.sort", "sort.key_width",
            "encoded.decode"} <= served
    # one update a query, the sort and its width: beside q6's two
    assert got["stage_dispatches"]["value"] >= 5
