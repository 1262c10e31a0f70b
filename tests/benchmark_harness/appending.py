"""What a PR that appends a cell does to the benchmark, and what a cell's test
file may assert about the lists it is on.

`appended_copy` makes, in a directory of its own, what the next `model_config`
PR leaves: a NEW configuration, traffic mix, cell and per-layer metric, their
entries appended to `BENCHMARK.json`. `lists_the_cell` is the one rule every
cell's test file holds its lists to: membership and first place, never the
end of a list, since a later cell is appended after every accepted one. The
`doc` fixture (`conftest.py`) runs each such assertion on the real manifest
and on the copy."""

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib.manifest import Manifest  # noqa: E402

#: the next cell's configuration, NEW: Q3 over a lineitem without
#: `l_shipdate`, a schema no accepted configuration has
NEXT_CONFIG, NEXT_CELL = "tpch-q3-noship", "q3_noship"
#: the accepted cell of the same query shape (join, group-by, top-N on one
#: chip): the next cell is appended to every list this one is on, and to no
#: other, as a metric lists only the cells its reader finds something in
LIKE = "q3_join_groupby_topn"


def appended_copy(root):
    """What the next `model_config` PR does, on a copy under `root`: a
    configuration, a traffic mix, a cell and a per-layer metric as NEW
    files, their entries appended to `BENCHMARK.json`, and the cell appended
    to the list of every metric `LIKE` reports. The configuration is
    `tpch-q3`'s with the ship date taken out of lineitem (`next_config/`: its
    query and reference). Returns the copy's manifest and the files it
    started from."""
    bench = root / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    newcfg = bench / "configs" / NEXT_CONFIG
    newcfg.mkdir()
    for f in ("query.py", "reference.py"):
        shutil.copy(HERE / "next_config" / f, newcfg / f)
    cfg = json.loads((bench / "configs" / "tpch-q3" / "config.json")
                     .read_text())
    cfg["name"] = NEXT_CONFIG
    del cfg["schema"]["lineitem"]["l_shipdate"]
    cfg["query"] = cfg["query"].replace(
        " AND l_shipdate > date '1995-03-15'", "")
    cfg["rehearse"]["scale"] = {"scale_factor": 0.004,
                                "lineitem_rows": 24005, "orders_rows": 6000,
                                "customer_rows": 600}
    (newcfg / "config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "closed1b.json").write_text(json.dumps(
        {"name": "closed1b", "loop": "closed", "clients": 1}))
    (bench / "workloads" / f"{NEXT_CELL}.json").write_text(json.dumps(
        {"name": NEXT_CELL, "config": NEXT_CONFIG,
         "traffic": "closed1b", "chips": 1, "trace_queries": 3}))
    (bench / "layer_metrics" / "all_dispatches.py").write_text(
        "def read(obs):\n"
        "    return obs.dispatches(lambda label: True) / obs.queries\n")

    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in doc["per_layer"]:
        if LIKE in m["workloads"]:
            m["workloads"].append(NEXT_CELL)
    doc["configs"].append({"name": NEXT_CONFIG, "source": cfg["source"],
                           "file": f"benchmarks/configs/{NEXT_CONFIG}/"
                                   "config.json",
                           "reduced": list(cfg["reduced"]), "why": "test"})
    doc["workloads"].append({"name": NEXT_CELL, "config": NEXT_CONFIG,
                             "traffic": "closed1b", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "all_dispatches", "unit": "count/query",
                             "better": "lower", "source": "program_counter",
                             "layer": "fused stages", "moves": "query_s",
                             "workloads": [NEXT_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return Manifest(root=str(root), bench=str(bench)), before


def lists_the_cell(doc, cell, metric, its_own, not_the_cells):
    """A metric the cell reports lists it; one the cell brought (`its_own`)
    lists it FIRST, and a later cell that runs the same mechanism follows
    it; one the cell has nothing to read for does not list it."""
    entry = {e["name"]: e for e in doc["per_layer"]}[metric]
    if metric in not_the_cells:
        assert cell not in entry["workloads"]
    elif metric in its_own:
        assert entry["workloads"][0] == cell and entry["moves"] == "query_s"
    else:
        assert cell in entry["workloads"]
        assert entry["workloads"][0] != cell        # an older cell's metric
