"""CPU tests of the per-layer reader `groupby_mask_share`: the lane tier's
updates that ran on the row mask of the filter/project chain their aggregate
absorbed, over all lane updates, from the WINDOW's delta of
`exec/aggregate.counters()`. The traced rehearsal of q1 finds it on its
result line (`test_tpch_q1.py`). None of this is a chip run."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import observe  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402


def _obs(**families):
    return observe.Observation(
        queries=2, window_s=1.0, window={"families": families}, setup={},
        trace=None, work={}, peaks={}, memory_peak_bytes=0)


@pytest.mark.parametrize("family,want", [
    # q1: one lane update a query, every one on the chain's mask
    ({"hash_updates": 19, "lane_updates": 19, "lane_masked_updates": 19,
      "mask_compactions": 0}, 100.0),
    # the chain compacted by a map stage of its own: lane updates, no mask
    ({"hash_updates": 19, "lane_updates": 19, "lane_masked_updates": 0,
      "mask_compactions": 0}, 0.0),
    # merges of partials ride lanes without a mask: counted beside them
    ({"hash_updates": 4, "lane_updates": 4, "lane_masked_updates": 3},
     75.0),
    # no lane update in the window (a lane leftover, then the hash rounds)
    ({"hash_updates": 1, "lane_updates": 0, "lane_masked_updates": 0,
      "lane_leftovers": 1, "mask_compactions": 1}, None),
    # a program with the lane tier and without the mask's counters
    ({"hash_updates": 15, "lane_updates": 15}, None),
    ({}, None),
])
def test_groupby_mask_share_is_the_windows_masked_over_its_lane_updates(
        family, want):
    read = Manifest().reader("groupby_mask_share")
    assert read(_obs(aggregate=family, upload={"direct_cols": 1})) == want


def test_a_program_without_the_counters_reads_none(monkeypatch):
    """A snapshot of a program whose aggregate module has no `counters()`
    carries an empty family, and the reader is silent on its delta."""
    from spark_rapids_tpu.exec import aggregate
    monkeypatch.delattr(aggregate, "counters", raising=False)
    a = observe.snapshot()
    d = observe.delta(a, observe.snapshot())
    assert d["families"]["aggregate"] == {}
    read = Manifest().reader("groupby_mask_share")
    assert read(_obs(**d["families"])) is None
    bare = observe.Observation(
        queries=2, window_s=1.0, window={"phases": {}}, setup={}, trace=None,
        work={}, peaks={}, memory_peak_bytes=0)
    assert read(bare) is None


def test_the_window_alone_is_counted_not_the_set_up():
    setup = {"lane_updates": 2, "lane_masked_updates": 0}
    after = {"lane_updates": 8, "lane_masked_updates": 6}
    snap = {"counters": {}, "labels": {}, "phases": {}}
    d = observe.delta({**snap, "families": {"aggregate": setup}},
                      {**snap, "families": {"aggregate": after}})
    read = Manifest().reader("groupby_mask_share")
    assert read(_obs(**d["families"])) == 100.0
