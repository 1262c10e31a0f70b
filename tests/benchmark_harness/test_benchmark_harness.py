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

HERE = Path(__file__).resolve().parent
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- the manifest --------------------------------------------------------------

def test_manifest_names_units_and_files_are_within_the_contract():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in DOC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in DOC["end_to_end"]}
    assert "setup_s" in e2e
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in DOC["configs"]:
        assert any(c["file"].startswith(p + "/") for p in DOC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in DOC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert 1 <= DOC["run_seconds"] <= 51
    for p in DOC["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert re.match(r"^[A-Za-z0-9_.\-/]+$",
                                str(f.relative_to(ROOT))), f


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


def test_query_s_is_the_whole_window_over_its_queries_and_shows_a_stall():
    steady = _drive([2.0] * 10, seconds=9.0)
    assert len(steady.completed) == 5            # the one in flight finishes
    assert steady.end - steady.start == pytest.approx(10.0)
    assert window.summarize(steady)["query_s"] == pytest.approx(2.0)
    stalled = _drive([2.0, 2.0, 7.0, 2.0, 2.0, 2.0], seconds=12.0)
    s = window.summarize(stalled)
    assert len(stalled.completed) == 4
    assert s["query_s"] == pytest.approx(13.0 / 4)
    assert s["query_s"] > 1.6 * 2.0              # the stall shows


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

@pytest.mark.parametrize("cell", CELLS)
def test_work_model_counts_the_querys_bytes_from_the_generated_rows(cell):
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell(cell)["config"]))
    ref = m.config_module(cfg, "reference")
    tables = ref.generate(7, cfg)
    work = ref.work_model(cfg, tables)
    assert all(w["bound"] == "memory" and w["bytes"] > 0 for w in work.values())
    rows = cfg["scale"]["lineitem_rows"]
    assert len(tables["lineitem"]["l_shipdate"]) == rows
    if cfg["name"] == "tpch-q6":
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
    assert res["attempted"] == len(res["per_query_s"]) >= 1
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in Manifest().metrics_of(cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert res["window_compiles"] == 0
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    json.loads(json.dumps(res))                  # the line is plain JSON


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_the_cells_layer_metrics(cell):
    res = _rehearse(cell, trace=True)
    m = Manifest()
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
    bench = tmp_path / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    newcfg = bench / "configs" / "tpch-q6-other"
    shutil.copytree(bench / "configs" / "tpch-q6", newcfg)
    cfg = json.loads((newcfg / "config.json").read_text())
    cfg["name"] = "tpch-q6-other"
    cfg["rehearse"]["scale"]["lineitem_rows"] = 23000
    (newcfg / "config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "closed1b.json").write_text(json.dumps(
        {"name": "closed1b", "loop": "closed", "clients": 1}))
    (bench / "workloads" / "q6_other.json").write_text(json.dumps(
        {"name": "q6_other", "config": "tpch-q6-other",
         "traffic": "closed1b", "chips": 1, "trace_queries": 3}))
    (bench / "layer_metrics" / "all_dispatches.py").write_text(
        "def read(obs):\n"
        "    return obs.dispatches(lambda label: True) / obs.queries\n")

    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tpch-q6-other", "source": cfg["source"],
                           "file": "benchmarks/configs/tpch-q6-other/config.json",
                           "reduced": list(cfg["reduced"]), "why": "test"})
    doc["workloads"].append({"name": "q6_other", "config": "tpch-q6-other",
                             "traffic": "closed1b", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "all_dispatches", "unit": "count/query",
                             "better": "lower", "source": "program_counter",
                             "layer": "fused stages", "moves": "query_s",
                             "workloads": ["q6_other"]})
    for m in doc["per_layer"]:
        if m["name"] in ("ingest_dispatches", "stage_dispatches"):
            m["workloads"].append("q6_other")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    res = harness.run_cell(
        "q6_other", 11, 0.5, True, require_tpu=False, rehearse=True,
        manifest=Manifest(root=str(tmp_path), bench=str(bench)))
    assert res["correct"] is True
    assert res["attempted"] == 3                  # the new cell's own trace_queries
    got = res["metrics"]
    assert set(got) == {"all_dispatches", "ingest_dispatches",
                        "stage_dispatches"}
    assert got["all_dispatches"]["value"] == pytest.approx(
        got["ingest_dispatches"]["value"] + got["stage_dispatches"]["value"])
    assert {p: p.read_bytes() for p in before} == before  # nothing edited
