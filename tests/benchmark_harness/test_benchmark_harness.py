"""CPU tests of the benchmark's own code (`benchmarks/`, BENCHMARK.json).
None of this is a chip run: the engine runs on the CPU backend at each
configuration's `rehearse` size, and no time measured here is reported.
"""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import harness, peaks, trace, window  # noqa: E402
from benchmarks.lib.manifest import (Manifest, ManifestError,  # noqa: E402
                                     apply_rehearsal)
from benchmarks.tools import spread_readings  # noqa: E402
from appending import NEXT_CELL, appended_copy  # noqa: E402

HERE = Path(__file__).resolve().parent
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- the manifest --------------------------------------------------------------

def manifest_within_the_contract(doc, root=ROOT):
    """The contract's limits on names, units and files, on `BENCHMARK.json`
    as `doc` has it and the files under `root`: the real manifest, and a
    copy with the next PR's entries appended (`test_manifest_append_only.py`)."""
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in doc["workloads"]]
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert len(m["workloads"]) == len(set(m["workloads"]))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        cfg = json.loads((Path(root) / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in doc["workloads"])
    for w in doc["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["config"] in {c["name"] for c in doc["configs"]}
    assert 1 <= doc["run_seconds"] <= 51
    for p in doc["paths"]:
        for f in (Path(root) / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert re.match(r"^[A-Za-z0-9_.\-/]+$",
                                str(f.relative_to(root))), f


def test_manifest_names_units_and_files_are_within_the_contract():
    manifest_within_the_contract(DOC)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    m = Manifest()
    e2e = {x["name"] for x in m.metrics_of(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = m.metrics_of(cell, "per_layer")
    assert layer
    for x in layer:
        assert callable(m.reader(x["name"]))
        assert x["moves"] in e2e, (cell, x["name"])
    c = m.cell(cell)
    assert c["trace_queries"] >= 1
    assert m.traffic(c["traffic"])["loop"] == "closed"


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(Manifest().reader(metric))


def test_plan_ms_is_retired_and_no_reader_is_left_without_an_entry():
    """PR 30: `plan_span_ms` is the planner's metric; the outside reading,
    its reader and the second planning after the window are gone."""
    named = {m["name"] for m in DOC["per_layer"]}
    assert "plan_ms" not in named and "plan_span_ms" in named
    readers = {f.stem for f in (ROOT / "benchmarks" / "layer_metrics").glob("*.py")}
    assert readers == named
    assert not hasattr(harness.observe.Observation(
        queries=1, window_s=1.0, window={}, setup={}, trace=None, work={},
        peaks={}, memory_peak_bytes=0), "plan_s")


def test_a_cell_the_manifest_does_not_name_is_refused():
    with pytest.raises(ManifestError, match="names no workload"):
        Manifest().cell("no_such_cell")


# -- the window's arithmetic ---------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _drive(durations, seconds):
    clock = FakeClock()
    it = iter(durations)

    def issue():
        clock.t += next(it)
        return "rows"

    return window.run_window(issue, {"loop": "closed", "clients": 1},
                             seconds, clock=clock)


STEADY = ([2.0] * 10, 9.0)                        # the one in flight finishes
STALLED = ([2.0, 2.0, 7.0, 2.0, 2.0, 2.0], 12.0)  # one stall, mid-window


@pytest.mark.parametrize("drive,queries,window_s,mean,median", [
    (STEADY, 5, 10.0, 2.0, 2.0),
    (STALLED, 4, 13.0, 13.0 / 4, 2.0),
], ids=["steady", "stalled"])
def test_the_windows_estimators_and_which_of_them_shows_a_stall(
        drive, queries, window_s, mean, median):
    w = _drive(*drive)
    assert len(w.completed) == queries
    assert w.end - w.start == pytest.approx(window_s)
    e = window.estimators([q.end - q.start for q in w.completed],
                          w.end - w.start)
    assert e["queries"] == queries                # the sample count
    assert e["window_mean_s"] == pytest.approx(mean)
    assert e["median_s"] == pytest.approx(median)
    assert "p90_s" not in e                       # no ten samples beyond it


@pytest.mark.parametrize("drive,queries,query_s", [
    (STEADY, 5, 2.0),
    (STALLED, 4, 13.0 / 4),
    # a stall in the LAST query, which runs past the window's seconds
    (([2.0, 2.0, 2.0, 9.0], 7.0), 4, 15.0 / 4),
], ids=["steady", "stalled", "stalled_at_the_close"])
def test_query_s_is_the_whole_window_over_its_queries_and_shows_a_stall(
        drive, queries, query_s):
    """The rule of PERF.md section 2 kept the window mean (PR 30): whatever
    the window spent, wherever, is in `query_s`; `queries` is its sample
    count."""
    s = window.summarize(_drive(*drive))
    assert s == {"query_s": pytest.approx(query_s), "queries": queries}
    if query_s > 2.0:
        assert s["query_s"] > 1.6 * 2.0           # the stall shows


@pytest.mark.parametrize("n,has_p90", [(99, False), (100, True), (110, True)])
def test_a_90th_percentile_is_read_only_with_ten_samples_beyond_it(n, has_p90):
    times = [0.4 + 0.001 * i for i in range(n)]   # 0.400, 0.401, ...
    e = window.estimators(times[::-1], sum(times))
    assert ("p90_s" in e) is has_p90
    if has_p90:
        # statistics.quantiles, n=10, exclusive: the 0.9 * (n + 1)-th sample
        assert e["p90_s"] == pytest.approx(0.4 + 0.001 * (0.9 * (n + 1) - 1))
        assert sum(t > e["p90_s"] for t in times) >= 10


def test_a_failed_query_is_counted_and_three_in_a_row_stop_the_client():
    clock = FakeClock()

    def issue():
        clock.t += 1.0
        raise RuntimeError("device fell over")

    w = window.run_window(issue, {"loop": "closed", "clients": 1}, 60.0,
                          clock=clock)
    assert len(w.queries) == 3 and not w.completed
    assert "device fell over" in w.queries[0].error
    assert window.summarize(w) == {}


def test_traced_window_stops_the_client_after_its_queries():
    w = window.run_window(lambda: "rows", {"loop": "closed", "clients": 1},
                          3600.0, max_queries=3)
    assert len(w.completed) == 3


@pytest.mark.parametrize("mix", [{"loop": "open"},
                                 {"loop": "closed", "clients": 2}])
def test_a_mix_the_generator_does_not_offer_is_refused(mix):
    with pytest.raises(ValueError, match="one closed-loop client"):
        window.run_window(lambda: 1, mix, 1.0)


# -- the trace reduction -------------------------------------------------------

def _ev(line, name, start_us, dur_us, plane="/device:TPU:0"):
    return trace.Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_reduction_busy_union_module_time_and_named_gaps():
    ev = [
        _ev("main", trace.WINDOW_MARK, 0, 1000, plane="/host:CPU"),
        _ev("main", "SourceScanExec", 0, 500, plane="/host:CPU"),
        _ev("main", "upload", 100, 50, plane="/host:CPU"),
        _ev("main", "CompiledStageExec", 500, 500, plane="/host:CPU"),
        # before the window: clipped away
        _ev(trace.OPS_LINE, "fusion.9", -50, 20),
        # two overlapping ops, then a gap, then one op
        _ev(trace.OPS_LINE, "fusion.1", 200, 100),
        _ev(trace.OPS_LINE, "copy.2", 250, 100),
        _ev(trace.OPS_LINE, "fusion.1", 600, 200),
        _ev(trace.MODULES_LINE, "jit_step(123456789)", 200, 150),
        _ev(trace.MODULES_LINE, "jit_step(123456789)", 600, 200),
        _ev(trace.MODULES_LINE, "jit_concat_pair(42)", 1200, 10),
    ]
    r = trace.reduce(ev)
    assert r.chips == 1
    assert r.window_s == pytest.approx(1000e-6)
    assert r.busy_s == pytest.approx(350e-6)     # union, not the 400 us sum
    assert r.idle_s == pytest.approx(650e-6)
    assert r.module_s == {"jit_step": pytest.approx(350e-6)}
    assert r.module_runs == {"jit_step": 2}
    assert r.device_ops[0] == ["fusion.1", pytest.approx(300e-6)]
    gaps = dict(r.idle_gaps)
    # 350..600 us sits under both exec spans' edge; its middle (475) is under
    # the scan; 0..200 has `upload` (the innermost) over its middle
    assert gaps["upload"] == pytest.approx(200e-6)
    assert gaps["SourceScanExec"] == pytest.approx(250e-6)
    assert gaps["CompiledStageExec"] == pytest.approx(200e-6)
    assert sum(gaps.values()) == pytest.approx(r.idle_s)


def test_reduction_refuses_a_trace_in_which_nothing_ran_on_the_device():
    host_only = [_ev("main", "x", 0, 10, plane="/host:CPU")]
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.reduce(host_only)
    with pytest.raises(ValueError, match="no operation ran"):
        trace.reduce(host_only + [_ev(trace.MODULES_LINE, "jit_x(1)", 0, 5)])


def test_roofline_share_reads_the_work_model_and_is_silent_without_a_trace():
    m = Manifest()
    read = m.reader("agg_stage_roofline")
    cfg = apply_rehearsal(m.config("tpch-q6"))
    ref = m.config_module(cfg, "reference")
    work = ref.work_model(cfg, ref.generate(7, cfg))
    pk = peaks.peaks_for("TPU v5 lite")

    class Obs:
        queries = 2
        peaks = pk
        trace = None
    Obs.work = work
    assert read(Obs) is None                      # no trace: nothing, never 0
    Obs.trace = trace.Reduced(1, 1.0, 0.5, {"jit__agg_spec_body": 0.02},
                              {"jit__agg_spec_body": 2},
                              [], [])
    least = work["agg_stage"]["bytes"] / 819e9
    assert read(Obs) == pytest.approx(100 * least * 2 / 0.02)
    Obs.work = {}                                 # not this cell's kernel
    assert read(Obs) is None
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


FIXTURE = HERE / "fixture_q1_tiny_v5e.xplane.pb.gz"


def test_reduction_on_the_recorded_chip_trace(tmp_path):
    """A trace recorded on a v5e chip (my chip run, PR 26): two q1 queries at
    the rehearsal size (20,000 rows, 6 row groups each). The numbers are the
    file's own; `trace_look.py` prints them."""
    import gzip
    raw = tmp_path / "fixture.xplane.pb"
    raw.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    r = trace.reduce(trace.load(str(raw)))
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.102840339)
    assert r.busy_s == pytest.approx(0.025168891)
    assert r.module_runs["jit__agg_spec_body"] == 2
    assert r.module_runs["jit__concat_pair"] == 10
    assert r.module_runs["jit__unpack_batch_impl"] == 12
    assert r.module_s["jit__agg_spec_body"] == pytest.approx(0.000248021)
    assert r.module_s["jit__concat_pair"] == pytest.approx(0.024301018)
    assert sum(r.module_s.values()) <= r.window_s
    assert len(r.device_ops) == 10 and len(r.idle_gaps) <= 10
    named = sum(s for _, s in r.idle_gaps)        # the ten largest names
    assert 0.99 * r.idle_s <= named <= r.idle_s * (1 + 1e-9)
    assert r.idle_gaps[0][0] != "unattributed"


@pytest.mark.parametrize("metric,want", [
    ("concat_busy_share", 100 * 0.024301018 / 0.025168891),
    ("device_idle_share", 100 * (0.102840339 - 0.025168891) / 0.102840339),
    ("query_hbm_share", 100 * (2 * 819e3 / 819e9) / 0.025168891),
    ("hbm_peak_gib", 1.5),
])
def test_trace_fed_readers_on_the_recorded_chip_trace(tmp_path, metric, want):
    import gzip
    raw = tmp_path / "fixture.xplane.pb"
    raw.write_bytes(gzip.decompress(FIXTURE.read_bytes()))

    class Obs:
        queries = 2
        peaks = peaks.peaks_for("TPU v5 lite")
        work = {"agg_stage": {"bytes": 819e3, "bound": "memory"}}
        memory_peak_bytes = 3 * 2**29
        trace = globals()["trace"].reduce(globals()["trace"].load(str(raw)))
    read = Manifest().reader(metric)
    assert read(Obs) == pytest.approx(want, rel=1e-6)
    Obs.trace, Obs.memory_peak_bytes = None, 0
    assert read(Obs) is None                      # nothing to read: silent


# -- the work models -----------------------------------------------------------

def work_model_counts_the_generated_rows(m, cell):
    """The cell's configuration at its rehearsal size: every table with a
    `<table>_rows` entry in `scale` has that many rows in every column,
    whatever its columns are, and every entry of the work model is
    memory-bound bytes. Returns the configuration, tables and work model."""
    cfg = apply_rehearsal(m.config(m.cell(cell)["config"]))
    ref = m.config_module(cfg, "reference")
    tables = ref.generate(7, cfg)
    work = ref.work_model(cfg, tables)
    assert all(w["bound"] == "memory" and w["bytes"] > 0 for w in work.values())
    counted = [t for t in tables if f"{t}_rows" in cfg["scale"]]
    assert counted, cfg["scale"]
    for t in counted:
        assert {len(c) for c in tables[t].values()} == \
            {cfg["scale"][f"{t}_rows"]}, t
    return cfg, tables, work


@pytest.mark.parametrize("cell", CELLS)
def test_work_model_counts_the_querys_bytes_from_the_generated_rows(cell):
    cfg, tables, work = work_model_counts_the_generated_rows(Manifest(), cell)
    if cfg["name"] == "tpch-q6":
        rows = cfg["scale"]["lineitem_rows"]
        assert work["agg_stage"]["bytes"] == rows * (3 * 8 + 4) + 8


def test_the_generator_keeps_the_specifications_distributions():
    """Clause 4.2.3: 1 to 7 lines an order, ship date within 121 days of an
    order date, discount in hundredths up to 0.10, price a function of
    quantity and part; and the same number of rows for every seed."""
    from benchmarks.lib import dbgen
    for seed in (5, 6):
        rng = np.random.default_rng(seed)
        lines = dbgen.lines_per_order(rng, 15000, 60012)
        assert lines.sum() == 60012 and lines.min() == 1 and lines.max() == 7
        li = dbgen.lineitems(rng, 60012, 0.01)
        assert {len(v) for v in li.values()} == {60012}
    assert li["l_shipdate"].min() >= dbgen.STARTDATE + 1
    assert li["l_shipdate"].max() <= dbgen.ENDDATE - 151 + 121
    assert set(np.round(li["l_discount"] * 100).astype(int)) == set(range(11))
    assert li["l_quantity"].min() == 1 and li["l_quantity"].max() == 50
    unit = li["l_extendedprice"] / li["l_quantity"]
    assert 900.0 <= unit.min() and unit.max() <= 2100.0
    epoch = np.datetime64("1970-01-01")
    assert dbgen.STARTDATE == (np.datetime64("1992-01-01") - epoch).astype(int)
    assert dbgen.ENDDATE == (np.datetime64("1998-12-31") - epoch).astype(int)


# -- refusals ------------------------------------------------------------------

def _run_command(args, cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", **(extra_env or {})})
    return subprocess.run([sys.executable, *DOC["command"][1:], *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_a_device_that_is_not_a_tpu_is_refused_without_a_result():
    r = _run_command(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                      "--trace", "0"], ROOT)
    assert r.returncode == 2
    assert r.stdout.strip() == "" and "needs a TPU" in r.stderr


def test_only_the_benchmark_and_no_program_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in DOC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_command(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                      "--trace", "0", "--rehearse"], tmp_path,
                     {"PYTHONPATH": ""})
    assert r.returncode != 0 and '"correct"' not in r.stdout


# -- a run, end to end on the CPU, sound and broken ----------------------------

def _rehearse(cell, **kw):
    kw.setdefault("trace", False)
    return harness.run_cell(cell, 3000000019, 0.5, require_tpu=False,
                            rehearse=True, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_prints_the_contracts_line(cell):
    res = _rehearse(cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == len(res["per_query_s"]) == res["queries"] >= 1
    assert res["metrics"]["query_s"]["value"] == pytest.approx(
        res["window_s"] / res["queries"])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in Manifest().metrics_of(cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert res["window_compiles"] == 0
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    json.loads(json.dumps(res))                  # the line is plain JSON


def traced_run_reports_the_layer_metrics(m, cell):
    """A traced rehearsal of `cell` under the manifest `m` reads every
    per-layer metric the cell is listed for, and no other. Returns the
    result line."""
    res = _rehearse(cell, trace=True, manifest=m)
    # on the CPU there is no device plane and no memory peak: the readers
    # fed by the trace or the allocator stay silent
    silent = {x["name"] for x in m.metrics_of(cell, "per_layer")
              if x["source"] == "device_trace"} | {"hbm_peak_gib"}
    want = {x["name"] for x in m.metrics_of(cell, "per_layer")} - silent
    assert set(res["metrics"]) == want
    assert res["attempted"] == m.cell(cell)["trace_queries"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["metrics"]["ingest_dispatches"]["value"] > 0
    assert res["metrics"]["stage_dispatches"]["value"] > 0
    assert res["correct"] is True
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_the_cells_layer_metrics(cell):
    traced_run_reports_the_layer_metrics(Manifest(), cell)


def _break_collect(monkeypatch, fault):
    from spark_rapids_tpu.api.session import DataFrame
    sound = DataFrame.collect
    state = {"n": 0}

    def broken(self):
        state["n"] += 1
        rows = sound(self)
        return fault(rows, state["n"])

    monkeypatch.setattr(DataFrame, "collect", broken)


def _nudge_double(rows, n, rel=1e-9):
    """An answer altered where it is produced: one DOUBLE of one row of every
    third query, by a billionth."""
    if n % 3 or not rows:
        return rows
    r = list(rows[0])
    i = next(i for i, v in enumerate(r) if isinstance(v, float))
    r[i] *= 1.0 + rel
    return [tuple(r)] + list(rows[1:])


def _lose_a_row(rows, n):
    """A sampled answer: the last row missing."""
    return list(rows[:-1])


def _nan_double(rows, n):
    """A DOUBLE that overflowed: not a number, in one row of one answer."""
    if n != 3 or not rows:
        return rows
    r = list(rows[0])
    i = next(i for i, v in enumerate(r) if isinstance(v, float))
    r[i] = float("nan")
    return [tuple(r)] + list(rows[1:])


def _never_answers(rows, n):
    if n > 2:
        raise RuntimeError("no answer")
    return rows


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,number", [
    (_nudge_double, "sum_rel_err"),
    (_nan_double, "sum_rel_err"),
    (_lose_a_row, None),
    (_never_answers, "unanswered"),
])
def test_a_broken_timed_path_comes_out_not_correct(cell, fault, number,
                                                   monkeypatch):
    _break_collect(monkeypatch, fault)
    res = _rehearse(cell)
    assert res["correct"] is False
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert over and (number is None or number in over), res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_float32_control_in_the_programs_place_is_not_correct(
        cell, monkeypatch):
    """The control of `correct`: the plain reference computed in float32 (the
    configurations state float64), answering in the program's place."""
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(cell)["config"]))
    ref = m.config_module(cfg, "reference")
    low = ref.as_rows(ref.reference(ref.generate(3000000019, cfg), cfg,
                                    np.float32))
    _break_collect(monkeypatch, lambda rows, n: low)
    res = _rehearse(cell)
    assert res["correct"] is False
    c = res["checks"]["sum_rel_err"]
    assert c["value"] > 3 * c["limit"], c


# -- later PRs add files and entries, and edit nothing -------------------------

def test_a_cell_config_traffic_and_metric_added_as_new_files_only(tmp_path):
    """The next PR's cell, appended to every list a Q3-shaped cell reports,
    is held to what every accepted cell is held to."""
    own, before = appended_copy(tmp_path)
    listed = {m["name"] for m in own.metrics_of(NEXT_CELL, "per_layer")}
    assert {"all_dispatches", "plan_reruns", "topn_busy_share",
            "groupby_fallbacks", "join_build_ms"} <= listed
    got = traced_run_reports_the_layer_metrics(own, NEXT_CELL)["metrics"]
    assert got["all_dispatches"]["value"] == pytest.approx(
        got["ingest_dispatches"]["value"] + got["stage_dispatches"]["value"])
    assert 0 < got["direct_pack_share"]["value"] < 100  # the segment strings
    assert {p: p.read_bytes() for p in before} == before  # nothing edited


# -- the tool that reads result lines the way the check does -------------------

RECORDED = HERE / "recorded_q6_set_pr30.jsonl"
# the six runs' `query_s` as the chip printed them (my chip runs, PR 30, set A:
# PR 28's tree, window mean, 51 s, six seeds, one call)
RECORDED_QUERY_S = [0.46057971536036035, 0.4448406172086956,
                    0.45199132153097343, 0.4494282023333332,
                    0.4600422864054051, 0.5009004195196076]


def test_spread_readings_on_recorded_lines_gives_the_spread_by_hand():
    tool = spread_readings
    lines = tool.result_lines(str(RECORDED))
    assert [r["metrics"]["query_s"]["value"] for r in lines] == RECORDED_QUERY_S
    got = tool.read_set(lines)
    mean = got["window_mean_s"]
    assert mean["runs"] == pytest.approx(RECORDED_QUERY_S)
    # by hand: the median lies between the third and fourth of the sorted six;
    # 0.5009 is the farthest from it and is left out; the range of the other
    # five is 0.46058 - 0.44484
    median = (0.45199132153097343 + 0.4600422864054051) / 2
    assert mean["median"] == pytest.approx(median)
    assert mean["range_drop1"] == pytest.approx(
        (0.46057971536036035 - 0.4448406172086956) / median)
    assert mean["range_drop1"] == pytest.approx(0.034514294, rel=1e-6)
    # the quartiles of all six, as statistics.quantiles(n=4) places them:
    # 1.75th and 5.25th of the sorted runs
    q1 = 0.4448406172086956 + 0.75 * (0.4494282023333332 - 0.4448406172086956)
    q3 = 0.46057971536036035 + 0.25 * (0.5009004195196076 - 0.46057971536036035)
    assert mean["iqr"] == pytest.approx((q3 - q1) / median)
    assert got["queries"]["runs"] == [111, 115, 113, 114, 111, 102]
    assert got["median_s"]["median"] < mean["median"]   # a long right tail
    assert set(got) == {"queries", "window_mean_s", "median_s", "p90_s"}


@pytest.mark.parametrize("values,want", [
    ([1.0, 1.01, 0.99, 1.02, 1.5], 0.03 / 1.01),    # the far one is left out
    ([1.0, 1.0, 1.0], 0.0),
    ([1.0, 1.1], 0.1 / 1.05),                       # two runs: their range
])
def test_range_drop1_leaves_out_the_run_farthest_from_the_median(values, want):
    assert spread_readings.range_drop1(values) == pytest.approx(want)


def test_spread_readings_runs_on_the_cpu_on_recorded_lines(tmp_path):
    """The command itself, on a directory of result files and on one file of
    lines; a set without a result line is refused."""
    d = tmp_path / "as_files"
    d.mkdir()
    for i, line in enumerate(RECORDED.read_text().splitlines()):
        (d / f"s{i}.json").write_text("bench +1.0s progress\n" + line + "\n")
    tool = str(ROOT / "benchmarks" / "tools" / "spread_readings.py")
    r = subprocess.run([sys.executable, tool, str(d), str(RECORDED), "--decide"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    a, b = doc["sets"]["as_files"], doc["sets"][RECORDED.name]
    assert a == b and doc["medians_apart"]["window_mean_s"] == 0.0
    assert doc["decision"]["query_s"] in ("window_mean_s", "median_s")
    assert doc["decision"]["bound"] in spread_readings.STEPS
    empty = tmp_path / "empty.jsonl"
    empty.write_text("not a result\n")
    r = subprocess.run([sys.executable, tool, str(empty)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 3 and "no result line" in r.stderr


def _sets(mean, median, p90=None):
    """Two or more sets with these spreads (farthest run left out)."""
    out = {}
    for i, (a, b) in enumerate(zip(mean, median)):
        out[f"set{i}"] = {"window_mean_s": {"range_drop1": a},
                          "median_s": {"range_drop1": b}}
        if p90 is not None:
            out[f"set{i}"]["p90_s"] = {"range_drop1": p90[i]}
    return out


@pytest.mark.parametrize("mean,median,p90,estimator,bound,twice,within,p90_bound", [
    # the median spreads as much as the mean: the mean stays, bound from it
    ([0.0345, 0.027], [0.0455, 0.03], [0.04, 0.04], "window_mean_s", 0.08, True, True, None),
    # exactly 0.6 of the mean's: the median wins, and brings its tail along
    ([0.02, 0.01], [0.012, 0.009], [0.04, 0.03], "median_s", 0.03, True, True, 0.08),
    # ... but not a tail that would need more than 0.10
    ([0.02, 0.01], [0.012, 0.009], [0.06, 0.03], "median_s", 0.03, True, True, None),
    # just over 0.6: the mean stays
    ([0.02, 0.02], [0.0121, 0.01], None, "window_mean_s", 0.05, True, True, None),
    # the floor: never under 0.01, even where that is over 8 x the narrowest
    ([0.002, 0.001], [0.002, 0.001], None, "window_mean_s", 0.01, True, False, None),
    # no step reaches twice the widest: 0.08 all the same, and said so
    ([0.05, 0.03], [0.05, 0.04], None, "window_mean_s", 0.08, False, False, None),
])
def test_the_rule_that_picks_the_estimator_and_the_bound(
        mean, median, p90, estimator, bound, twice, within, p90_bound):
    d = spread_readings.decide(_sets(mean, median, p90))
    assert d["query_s"] == estimator and d["bound"] == bound
    assert d["bound_reaches_twice_the_widest"] is twice
    assert d["bound_within_8x_narrowest"] is within
    if estimator == "median_s" and p90 is not None:
        assert d["query_p90_s"]["bound"] == p90_bound
        assert d["query_p90_s"]["added"] is (p90_bound is not None)
    else:
        assert "query_p90_s" not in d
