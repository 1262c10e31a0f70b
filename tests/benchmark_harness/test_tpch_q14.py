"""CPU tests of what the configuration `tpch-q14` and its cell
`q14_join_like_ratio` add (ISSUE 31): the generator of `part` and of the
lineitem columns that join it, the session path against the plain reference
at the rehearsal size, and the four per-layer readers. The cell-parametrised
tests of `test_benchmark_harness.py` pick the cell up from `BENCHMARK.json`
by themselves (sound run, traced run, four faults, float32 control, work
model). None of this is a chip run."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import datagen, dbgen, dbgen_part, peaks, trace  # noqa: E402
from benchmarks.lib.manifest import (Manifest, apply_rehearsal,  # noqa: E402
                                     manifest_at)

CELL = "q14_join_like_ratio"
PROBE, DECODE, CONCAT = "jit__ja_spec_body", "jit__decode", "jit__concat_pair"
LABELS = {PROBE: ["CompiledStageExec.probe_step"],
          "jit__sizing_body": ["CompiledStageExec.sizing"],
          DECODE: ["encoded.decode"],
          CONCAT: ["coalesce.concat_pair"]}


@pytest.fixture(scope="module")
def config():
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(CELL)["config"]))
    return cfg, m.config_module(cfg, "reference"), m.config_module(cfg, "query")


# -- the generator -------------------------------------------------------------

def test_part_and_its_keys_keep_the_specifications_rules():
    """Clause 4.2.2.13: P_TYPE is three syllables, 6 x 5 x 5 = 150 values, a
    sixth of them PROMO. Clause 4.2.3: P_PARTKEY dense in 1..n, L_PARTKEY
    uniform over the parts (each has its part), price a function of quantity
    and part; the same number of rows for every seed."""
    assert len(set(dbgen_part.P_TYPES)) == 150
    assert sum(t.startswith("PROMO") for t in dbgen_part.P_TYPES) == 25
    assert dbgen_part.part_count(1.0) == 200_000
    sizes = set()
    for seed in (5, 6):
        rng = np.random.default_rng(seed)
        li = dbgen_part.lineitems_with_partkey(rng, 60012, 0.01)
        part = dbgen_part.parts(rng, dbgen_part.part_count(0.01))
        sizes.add((len(li["l_partkey"]), len(part["p_partkey"])))
        assert {len(v) for v in li.values()} == {60012}
        assert part["p_partkey"].tolist() == list(range(1, 2001))
        assert set(part["p_type"]) <= set(dbgen_part.P_TYPES)
        assert len(set(part["p_type"])) > 140          # 2000 draws of 150
        promo = np.mean([t.startswith("PROMO") for t in part["p_type"]])
        assert 0.13 < promo < 0.21                     # a sixth
        assert li["l_partkey"].dtype == np.int64
        assert li["l_partkey"].min() >= 1 and li["l_partkey"].max() <= 2000
        assert len(np.unique(li["l_partkey"])) == 2000  # every part is sold
        unit = np.round(li["l_extendedprice"] / li["l_quantity"] * 100)
        assert (unit == dbgen.retail_price_cents(li["l_partkey"])).all()
        assert li["l_shipdate"].min() >= dbgen.STARTDATE + 1
        assert li["l_shipdate"].max() <= dbgen.ENDDATE - 151 + 121
    assert sizes == {(60012, 2000)}


def test_generate_gives_the_configurations_tables_and_types(config, tmp_path):
    cfg, ref, _ = config
    tables = ref.generate(11, cfg)
    assert {t: list(cols) for t, cols in tables.items()} == \
        {t: list(cols) for t, cols in cfg["schema"].items()}
    assert len(tables["lineitem"]["l_partkey"]) == cfg["scale"]["lineitem_rows"]
    assert len(tables["part"]["p_partkey"]) == cfg["scale"]["part_rows"]
    same = ref.generate(11, cfg)
    assert all((np.asarray(tables[t][c]) == np.asarray(same[t][c])).all()
               for t in tables for c in tables[t])
    # `p_type` reaches Parquet as a string column (`lib/datagen.py` gives
    # pyarrow no type for it), dictionary-encoded as Parquet writes one
    import pyarrow as pa
    import pyarrow.parquet as pq
    paths = datagen.write_tables(str(tmp_path), tables, cfg["schema"],
                                 cfg["layout"])
    (part_file,) = sorted(Path(paths["part"]).parent.glob("*.parquet"))
    schema = pq.read_schema(part_file)
    assert schema.field("p_type").type == pa.string()
    assert schema.field("p_partkey").type == pa.int64()
    assert pq.ParquetFile(part_file).num_row_groups == 1
    assert ref.date_range(cfg) == (9374, 9404)        # 1995-09-01, 1995-10-01


# -- the session path against the plain reference ------------------------------

def _collect(cfg, query, tables, where):
    from spark_rapids_tpu.api.session import TpuSession
    paths = datagen.write_tables(str(where), tables, cfg["schema"],
                                 cfg["layout"])
    return query.build(TpuSession(dict(cfg["session_conf"])), paths,
                       cfg).collect()


def _no_promo(tables, cfg, ref):
    part = dict(tables["part"])
    part["p_type"] = [t.replace("PROMO", "LARGE") for t in part["p_type"]]
    return {**tables, "part": part}


def _empty_month(tables, cfg, ref):
    lo, hi = ref.date_range(cfg)
    line = dict(tables["lineitem"])
    ship = line["l_shipdate"].copy()
    ship[(ship >= lo) & (ship < hi)] = hi
    line["l_shipdate"] = ship
    return {**tables, "lineitem": line}


def _a_part_without_lines(tables, cfg, ref):
    """An inner join: a line whose part is gone is dropped, by both."""
    part = {k: v[:-40] for k, v in tables["part"].items()}
    return {**tables, "part": part}


@pytest.mark.parametrize("seed,alter,want", [
    (3000000019, None, "ratio"),
    (2147483659, None, "ratio"),        # past 32 signed bits
    (17, None, "ratio"),
    (17, _no_promo, 0.0),
    (17, _empty_month, None),
    (17, _a_part_without_lines, "ratio"),
], ids=["seed_a", "seed_b", "seed_c", "no_promo", "empty_month",
        "parts_missing"])
def test_the_session_path_equals_the_plain_reference(config, tmp_path, seed,
                                                     alter, want):
    cfg, ref, query = config
    tables = ref.generate(seed, cfg)
    if alter is not None:
        tables = alter(tables, cfg, ref)
    answer = ref.reference(tables, cfg)
    if want == "ratio":
        assert 5.0 < answer[0][0] < 30.0              # about a sixth, in %
    else:
        assert answer == [(want,)]
    rows = _collect(cfg, query, tables, tmp_path)
    got = ref.compare(rows, answer)
    assert got["rows_wrong"] == 0, (rows, answer)
    assert got["sum_rel_err"] <= cfg["limits"]["sum_rel_err"], (rows, answer)


def test_compare_counts_a_wrong_null_and_a_value_that_is_not_finite(config):
    _, ref, _ = config
    assert ref.compare([(None,)], [(None,)]) == \
        {"rows_wrong": 0, "sum_rel_err": 0.0}
    assert ref.compare([(None,)], [(16.5,)])["rows_wrong"] == 1
    assert ref.compare([(0.0,)], [(None,)])["rows_wrong"] == 1
    assert ref.compare([], [(16.5,)])["rows_wrong"] == 1
    assert ref.compare([(float("nan"),)], [(16.5,)])["sum_rel_err"] == \
        float("inf")
    assert ref.compare([(16.5 * (1 + 1e-9),)], [(16.5,)])["sum_rel_err"] == \
        pytest.approx(1e-9, rel=1e-3)
    assert ref.compare([(1e-12,)], [(0.0,)])["sum_rel_err"] == 1e-12


def test_the_work_model_counts_both_tables_and_the_strings_as_stored(config):
    cfg, ref, _ = config
    tables = ref.generate(7, cfg)
    work = ref.work_model(cfg, tables)
    strings = sum(len(t) for t in tables["part"]["p_type"])
    assert work == {"join_probe": {
        "bytes": cfg["scale"]["lineitem_rows"] * (8 + 8 + 8 + 4)
        + cfg["scale"]["part_rows"] * 8 + strings + 8, "bound": "memory"}}


# -- the four readers ----------------------------------------------------------

class Obs:
    """A hand-made observation: what `lib/observe.Observation` carries."""

    def __init__(self, queries=2, phases=None, labels=None, trace=None,
                 work=None):
        self.queries = queries
        self.window = {"phases": phases or {}, "labels": labels or {}}
        self.trace = trace
        self.work = work or {}
        self.peaks = peaks.peaks_for("TPU v5 lite")

    def dispatches(self, keep):
        return sum(rec["dispatches"]
                   for label, rec in self.window["labels"].items()
                   if keep(label))


def _reduced(module_s, busy_s=3.0):
    return trace.Reduced(1, 3.6, busy_s, dict(module_s),
                         {m: 2 for m in module_s}, [], [])


@pytest.fixture
def module_labels(monkeypatch):
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


def test_the_join_readers_find_the_joins_programs_by_their_labels(
        module_labels):
    m = Manifest()
    share, roofline = m.reader("join_busy_share"), m.reader("join_probe_roofline")
    work = {"join_probe": {"bytes": 819e6, "bound": "memory"}}   # 1 ms
    red = _reduced({PROBE: 1.6, "jit__sizing_body": 0.2, DECODE: 1.0,
                    CONCAT: 0.1, "jit_gather": 0.1})
    module_labels(LABELS)
    assert share(Obs(trace=red, work=work)) == pytest.approx(100 * 1.8 / 3.0)
    assert roofline(Obs(trace=red, work=work)) == pytest.approx(
        100 * 2 * 1e-3 / 1.8)
    # the per-operator join's programs are the join's too
    module_labels({**LABELS, "jit__build_kernel": ["HashJoinExec.build"]})
    red2 = _reduced({PROBE: 1.0, "jit__build_kernel": 0.5})
    assert share(Obs(trace=red2, work=work)) == pytest.approx(50.0)
    # another cell's work model: the roofline has nothing to divide
    assert roofline(Obs(trace=red, work={"agg_stage": {"bytes": 1}})) is None


def test_the_join_readers_are_silent_where_nothing_can_be_read(
        module_labels, monkeypatch):
    m = Manifest()
    work = {"join_probe": {"bytes": 819e6, "bound": "memory"}}
    red = _reduced({PROBE: 1.6, CONCAT: 0.1})
    for name in ("join_busy_share", "join_probe_roofline"):
        read = m.reader(name)
        module_labels(LABELS)
        assert read(Obs(trace=None, work=work)) is None       # no trace
        assert read(Obs(trace=_reduced({CONCAT: 0.1}), work=work)) is None
        module_labels({})                                     # ledger off
        assert read(Obs(trace=red, work=work)) is None        # never 0
        # one module serving the join and something else: cannot be split
        module_labels({**LABELS, PROBE: ["CompiledStageExec.probe_step",
                                         "CompiledStageExec.step"]})
        assert read(Obs(trace=red, work=work)) is None
        from spark_rapids_tpu.obs import dispatch
        monkeypatch.delattr(dispatch, "module_labels")        # before PR 27
        assert read(Obs(trace=red, work=work)) is None
        monkeypatch.undo()


def test_join_build_ms_reads_its_phase_and_is_silent_without_it():
    read = Manifest().reader("join_build_ms")
    assert read(Obs(2, {"join-build": 13_000_000, "plan": 5})) == \
        pytest.approx(6.5)
    assert read(Obs(2, {"join-build": 0})) == 0.0     # a time of nothing
    assert read(Obs(2, {"plan": 5})) is None          # the parent's program
    assert read(Obs(0, {"join-build": 13_000_000})) is None


def test_join_sizing_dispatches_counts_the_cold_paths_program():
    read = Manifest().reader("join_sizing_dispatches")
    labels = {"CompiledStageExec.sizing": {"dispatches": 1},
              "CompiledStageExec.probe_step": {"dispatches": 4}}
    assert read(Obs(4, labels=labels)) == 0.25
    assert read(Obs(4, labels={"CompiledStageExec.probe_step":
                               {"dispatches": 4}})) == 0.0   # a warm process
    assert read(Obs(0, labels=labels)) is None


def test_the_traced_rehearsal_reports_the_joins_span_and_counter(tmp_path):
    from benchmarks.lib import harness
    # a root of its own: another worker rehearses this cell in the checkout
    res = harness.run_cell(CELL, 2147483659, 0.5, True, require_tpu=False,
                           rehearse=True, manifest=manifest_at(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert got["join_build_ms"]["unit"] == "ms/query"
    assert got["join_build_ms"]["value"] > 0
    # two warm-up collects left the size cache warm: no sizing in the window
    assert got["join_sizing_dispatches"]["value"] == 0
    # on the CPU there is no device plane: the two trace-fed ones are silent
    assert "join_busy_share" not in got and "join_probe_roofline" not in got
    # the stage's probe step ran once a query, whatever else did
    assert got["stage_dispatches"]["value"] >= 1
