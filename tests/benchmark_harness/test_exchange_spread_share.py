"""CPU tests of the per-layer reader `exchange_spread_share`: the mesh
exchange's rounds dealt out by rows over the chips before the all-to-all, over
all its rounds, from the WINDOW's dispatch ledger (one `measure` a round, one
`spread` a spread round). The traced rehearsal of `q3_join_x4` finds it on its
result line (`test_benchmark_harness.py`). None of this is a chip run."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import observe  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

MEASURE, SPREAD = "ShuffleExchangeExec.measure", "ShuffleExchangeExec.spread"


def _obs(labels):
    window = {"labels": {k: {"dispatches": v, "traces": 0, "compile_ns": 0}
                         for k, v in labels.items()}}
    return observe.Observation(
        queries=4, window_s=1.0, window=window, setup={}, trace=None,
        work={}, peaks={}, memory_peak_bytes=0)


@pytest.mark.parametrize("labels,want", [
    # q3 on four chips: lineitem's and the joined orders' rounds spread,
    # the partial groups' round (already on four chips) does not
    ({MEASURE: 12, SPREAD: 8, "ShuffleExchangeExec.exchange_step": 12},
     100.0 * 8 / 12),
    # a program that deals a one-chip round out by batch: rounds, no spread
    ({MEASURE: 12, "ShuffleExchangeExec.stack": 48}, 0.0),
    ({MEASURE: 3, SPREAD: 3}, 100.0),
    # no mesh exchange in the window (a one-chip cell)
    ({"HashJoinExec.probe": 4}, None),
    ({SPREAD: 2}, None),
    ({}, None),
])
def test_exchange_spread_share_is_spread_rounds_over_measured_rounds(
        labels, want):
    read = Manifest().reader("exchange_spread_share")
    got = read(_obs(labels))
    assert got == (None if want is None else pytest.approx(want))


def test_the_window_alone_is_counted_not_the_set_up():
    snap = {"counters": {}, "phases": {}, "families": {}}
    setup = {MEASURE: {"dispatches": 6, "traces": 3, "compile_ns": 1},
             SPREAD: {"dispatches": 4, "traces": 2, "compile_ns": 1}}
    after = {MEASURE: {"dispatches": 18, "traces": 3, "compile_ns": 1},
             SPREAD: {"dispatches": 12, "traces": 2, "compile_ns": 1}}
    d = observe.delta({**snap, "labels": setup}, {**snap, "labels": after})
    obs = observe.Observation(queries=4, window_s=1.0, window=d, setup={},
                              trace=None, work={}, peaks={},
                              memory_peak_bytes=0)
    assert Manifest().reader("exchange_spread_share")(obs) == \
        pytest.approx(100.0 * 8 / 12)


def test_the_entry_is_appended_last_for_the_four_chip_cell(doc):
    """The entry as it was accepted, appended last at the time: right after
    `exchange_roofline`, the last metric accepted before it, wherever later
    ones end the list. The four-chip cell comes first in its list, and a
    later cell with a mesh exchange may follow."""
    names = [e["name"] for e in doc["per_layer"]]
    assert names.index("exchange_spread_share") == \
        names.index("exchange_roofline") + 1
    entry = dict(doc["per_layer"][names.index("exchange_spread_share")])
    cells = entry.pop("workloads")
    assert entry == {"name": "exchange_spread_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "exchange", "moves": "query_s"}
    assert cells[0] == "q3_join_x4"
    layer_of = {e["name"]: e["layer"] for e in doc["per_layer"]}
    assert layer_of["exchange_ms"] == layer_of["exchange_busy_share"] == \
        entry["layer"]
