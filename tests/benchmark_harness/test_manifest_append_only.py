"""CPU tests of the one rule a PR that is not a `benchmark` PR keeps
(ISSUE 37): every entry the accepted benchmark has is still in
`BENCHMARK.json`, unchanged but for cells appended to its `workloads`, and in
its order, at the head of its list. `accepted_manifest.json` beside this
file is what was accepted; only a `benchmark` PR rewrites it, a PR that
appends never needs to. The second half keeps the harness OPEN: on a copy
with the next PR's configuration, cell and metric appended, the rule and
every manifest assertion of the other test files hold."""

import copy
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_benchmark_harness as the_harness  # noqa: E402
import test_decode_busy_share as the_decode  # noqa: E402
import test_tpch_q1 as the_q1  # noqa: E402
from benchmarks.lib.manifest import LISTS, append_only  # noqa: E402

ACCEPTED = json.loads((HERE / "accepted_manifest.json").read_text())
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
Q6, Q14, Q1 = "q6_scan_filter_sum", "q14_join_like_ratio", \
    "q1_groupby_string_keys"


def _entry(doc, group, name):
    return {e["name"]: e for e in doc[group]}[name]


def test_the_manifest_holds_what_was_accepted_in_its_place():
    assert append_only(DOC, ACCEPTED) == []
    assert set(ACCEPTED) == set(LISTS)


# -- what a PR that appends may do ---------------------------------------------

def _append_a_metric(doc):
    doc["per_layer"].append({"name": "new_share", "unit": "%",
                             "better": "higher", "source": "program_counter",
                             "layer": "device", "moves": "query_s",
                             "workloads": [Q6]})


def _append_a_cell_to_lists(doc):
    doc["workloads"].append({"name": "q3", "config": "tpch-q1",
                             "traffic": "closed1", "chips": 1, "why": "w"})
    for name in ("ingest_dispatches", "groupby_ms", "join_busy_share",
                 "decode_busy_share"):
        _entry(doc, "per_layer", name)["workloads"].append("q3")


def _rewrite_the_prose(doc):
    _entry(doc, "workloads", Q6)["why"] = "what the cell reads now"
    _entry(doc, "configs", "tpch-q14")["why"] = "said otherwise"


def _tighten_a_bound(doc):
    _entry(doc, "end_to_end", "query_s")["bound"] = 0.05


@pytest.mark.parametrize("edit", [_append_a_metric, _append_a_cell_to_lists,
                                  _rewrite_the_prose, _tighten_a_bound])
def test_appending_prose_and_a_tighter_bound_are_nothing_wrong(edit):
    doc = copy.deepcopy(DOC)
    edit(doc)
    assert doc != DOC and append_only(doc, ACCEPTED) == []


# -- what it may not, each named -----------------------------------------------

def _insert(group, at=1):
    def edit(doc):
        doc[group].insert(at, {**doc[group][0], "name": "pushed_in"})
    return edit


def _remove(doc):
    doc["per_layer"].remove(_entry(doc, "per_layer", "upload_ms"))


def _swap(doc):
    m = doc["per_layer"]
    m[3], m[4] = m[4], m[3]


def _take_a_cell_out(doc):
    _entry(doc, "per_layer", "ingest_stall_ms")["workloads"].remove(Q14)


def _insert_a_cell_before_the_last(doc):
    _entry(doc, "per_layer", "groupby_ms")["workloads"].insert(0, "q3")


def _set(group, name, key, value):
    def edit(doc):
        _entry(doc, group, name)[key] = value
    return edit


def _drop_a_key(doc):
    del _entry(doc, "per_layer", "agg_stage_roofline")["workloads"]


FORBIDDEN = [
    ("inserted_in_per_layer", _insert("per_layer", 25),
     "per_layer: ['pushed_in'] stand before the accepted entry"),
    ("inserted_in_configs", _insert("configs"),
     "configs: ['pushed_in'] stand before the accepted entry 'tpch-q1'"),
    ("inserted_in_workloads", _insert("workloads", 0),
     "workloads: ['pushed_in'] stand before the accepted entry"),
    ("inserted_in_end_to_end", _insert("end_to_end"),
     "end_to_end: ['pushed_in'] stand before the accepted entry 'setup_s'"),
    ("removed", _remove, "per_layer: accepted entry 'upload_ms' is gone"),
    ("swapped", _swap,
     "per_layer: 'compile_s' stands where the accepted order has "
     "'stage_dispatches'"),
    ("cell_taken_out", _take_a_cell_out,
     "per_layer: ingest_stall_ms: `workloads`"),
    ("cell_before_the_last", _insert_a_cell_before_the_last,
     "per_layer: groupby_ms: `workloads`"),
    ("unit", _set("per_layer", "upload_ms", "unit", "s/query"),
     "per_layer: upload_ms: 'unit' changed from 'ms/query' to 's/query'"),
    ("better", _set("per_layer", "direct_pack_share", "better", "lower"),
     "per_layer: direct_pack_share: 'better' changed"),
    ("source", _set("end_to_end", "query_s", "source", "device_trace"),
     "end_to_end: query_s: 'source' changed"),
    ("layer", _set("per_layer", "decode_busy_share", "layer", "fused stages"),
     "per_layer: decode_busy_share: 'layer' changed"),
    ("moves", _set("per_layer", "compile_s", "moves", "query_s"),
     "per_layer: compile_s: 'moves' changed from 'setup_s' to 'query_s'"),
    ("bound_raised", _set("end_to_end", "query_s", "bound", 0.1),
     "end_to_end: query_s: `bound` raised from 0.08 to 0.1"),
    ("configs_source", _set("configs", "tpch-q6", "source", "TPC-H Q6"),
     "configs: tpch-q6: 'source' changed"),
    ("configs_file", _set("configs", "tpch-q14", "file",
                          "benchmarks/configs/tpch-q14/other.json"),
     "configs: tpch-q14: 'file' changed"),
    ("configs_reduced", _set("configs", "tpch-q1", "reduced", ["scale"]),
     "configs: tpch-q1: 'reduced' changed from [] to ['scale']"),
    ("cells_config", _set("workloads", Q1, "config", "tpch-q6"),
     "workloads: q1_groupby_string_keys: 'config' changed"),
    ("cells_chips", _set("workloads", Q14, "chips", 4),
     "workloads: q14_join_like_ratio: 'chips' changed from 1 to 4"),
    ("key_dropped", _drop_a_key,
     "per_layer: agg_stage_roofline: key 'workloads' removed"),
    ("key_added", _set("per_layer", "sort_ms", "why", "a note"),
     "per_layer: sort_ms: key 'why' added"),
]


@pytest.mark.parametrize("edit,named", [f[1:] for f in FORBIDDEN],
                         ids=[f[0] for f in FORBIDDEN])
def test_an_edit_of_what_was_accepted_is_named_wrong(edit, named):
    doc = copy.deepcopy(DOC)
    edit(doc)
    wrong = append_only(doc, ACCEPTED)
    assert len(wrong) == 1 and wrong[0].startswith(named), wrong


# -- the harness stays open: the next PR's copy passes everything ---------------

@pytest.fixture(scope="module")
def next_pr(tmp_path_factory):
    """`BENCHMARK.json` as the next `model_config` PR would leave it, with
    its new files, in a root of its own."""
    root = tmp_path_factory.mktemp("next_pr")
    own, _ = the_harness.appended_copy(root)
    appended = [m["name"] for m in own.doc["per_layer"]
                if "q6_other" in m["workloads"]]
    assert len(appended) >= 20 and appended[-1] == "all_dispatches"
    return own.doc, root


def _rule(doc, root):
    assert append_only(doc, ACCEPTED) == []


def _contract(doc, root):
    the_harness.manifest_within_the_contract(doc, root)


def _decode(doc, root):
    the_decode.manifest_reports_it_from_the_q14_cell_on(doc)


def _q1_cell(doc, root):
    the_q1.manifest_names_the_cell_and_its_configuration(doc)


def _q1_lists(doc, root):
    for metric in the_q1.REPORTS + the_q1.ITS_OWN + the_q1.NOT_THE_CELLS:
        the_q1.manifest_lists_the_cell(doc, metric)


@pytest.mark.parametrize("holds", [_rule, _contract, _decode, _q1_cell,
                                   _q1_lists])
def test_the_next_prs_manifest_passes_what_the_real_one_passes(next_pr, holds):
    """The same functions each file runs on the real manifest."""
    holds(*next_pr)
