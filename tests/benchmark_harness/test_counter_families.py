"""CPU tests of the counter families `lib/observe.py` snapshots beside the
ledgers (ISSUE 37) and of the two per-layer readers fed by them alone:
`groupby_lane_share` (`exec/aggregate.counters()`) and `direct_pack_share`
(`columnar/upload.counters()`), each over the WINDOW's delta. The traced
rehearsals of the three cells find both on their result lines
(`test_benchmark_harness.py`, `test_tpch_q1.py`). None of this is a chip
run."""

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


@pytest.fixture
def counters(monkeypatch):
    """Put counters in the place of the program's two families."""
    from spark_rapids_tpu.columnar import upload
    from spark_rapids_tpu.exec import aggregate

    def stub(aggregate_counters, upload_counters):
        for mod, c in ((aggregate, aggregate_counters),
                       (upload, upload_counters)):
            if c is None:                       # a program older than them
                monkeypatch.delattr(mod, "counters", raising=False)
            else:
                monkeypatch.setattr(mod, "counters", lambda c=c: dict(c),
                                    raising=False)
    return stub


def test_a_snapshot_carries_the_families_and_delta_subtracts_them(counters):
    counters({"executions": 2, "hash_updates": 2, "lane_updates": 2},
             {"direct_cols": 8, "built_cols": 4, "uploads": 3})
    a = observe.snapshot()
    assert a["families"] == {
        "aggregate": {"executions": 2, "hash_updates": 2, "lane_updates": 2},
        "upload": {"direct_cols": 8, "built_cols": 4, "uploads": 3}}
    # a counter that was not there at the first snapshot counts from nought
    counters({"executions": 6, "hash_updates": 7, "lane_updates": 5,
              "lane_declines": 1},
             {"direct_cols": 28, "built_cols": 12, "uploads": 8})
    d = observe.delta(a, observe.snapshot())
    assert d["families"] == {
        "aggregate": {"executions": 4, "hash_updates": 5, "lane_updates": 3,
                      "lane_declines": 1},
        "upload": {"direct_cols": 20, "built_cols": 8, "uploads": 5}}
    assert set(d) == {"counters", "labels", "phases", "families"}


def test_a_program_without_the_counters_reads_as_empty_families(counters):
    counters(None, None)
    a = observe.snapshot()
    assert a["families"] == {"aggregate": {}, "upload": {}}
    d = observe.delta(a, observe.snapshot())
    assert d["families"] == {"aggregate": {}, "upload": {}}
    m = Manifest()
    for metric in ("groupby_lane_share", "direct_pack_share",
                   "groupby_fallbacks"):
        assert m.reader(metric)(_obs(**d["families"])) is None
    # a family that appears between two snapshots counts from nought
    counters({"executions": 3}, None)
    assert observe.delta(a, observe.snapshot())["families"]["aggregate"] == \
        {"executions": 3}


@pytest.mark.parametrize("family,want", [
    ({"hash_updates": 15, "lane_updates": 15}, 100.0),   # Q1 since PR 36
    ({"hash_updates": 8, "lane_updates": 6, "lane_declines": 2}, 75.0),
    ({"hash_updates": 3, "lane_updates": 0}, 0.0),       # PR 35's program ran
    ({"hash_updates": 0, "lane_updates": 0}, None),      # no hash update ran
    ({"hash_updates": 3}, None),                         # before PR 36
    ({}, None),
])
def test_groupby_lane_share_is_the_windows_lane_updates_over_its_hash_updates(
        family, want):
    read = Manifest().reader("groupby_lane_share")
    assert read(_obs(aggregate=family, upload={"direct_cols": 1})) == want


@pytest.mark.parametrize("family,want", [
    ({"direct_cols": 128, "built_cols": 0}, 100.0),                 # q6
    ({"direct_cols": 129, "built_cols": 1}, 100 * 129 / 130),       # q14
    ({"direct_cols": 160, "built_cols": 64}, 100 * 5 / 7),          # q1
    ({"direct_cols": 0, "built_cols": 9}, 0.0),          # every column built
    ({"direct_cols": 0, "built_cols": 0}, None),         # nothing was packed
    ({"built_cols": 4, "uploads": 4}, None),             # before PR 34
    ({}, None),
])
def test_direct_pack_share_is_the_windows_direct_columns_over_all_packed(
        family, want):
    read = Manifest().reader("direct_pack_share")
    got = read(_obs(upload=family, aggregate={"hash_updates": 1}))
    assert got == (want if want is None else pytest.approx(want))


def test_a_reader_without_families_on_the_observation_is_silent():
    bare = observe.Observation(
        queries=2, window_s=1.0, window={"phases": {}}, setup={}, trace=None,
        work={}, peaks={}, memory_peak_bytes=0)
    m = Manifest()
    assert m.reader("groupby_lane_share")(bare) is None
    assert m.reader("direct_pack_share")(bare) is None


def test_the_window_alone_is_counted_not_the_set_up():
    """What `groupby_fallbacks` read until PR 37 was the process's counters:
    a set-up that left its tier would have shown in every window."""
    setup = {"executions": 2, "hash_round_retries": 2, "exact_fallbacks": 0,
             "hash_updates": 2, "lane_updates": 0}
    after = {"executions": 8, "hash_round_retries": 2, "exact_fallbacks": 0,
             "hash_updates": 8, "lane_updates": 6}
    snap = {"counters": {}, "labels": {}, "phases": {}}
    d = observe.delta({**snap, "families": {"aggregate": setup}},
                      {**snap, "families": {"aggregate": after}})
    m = Manifest()
    assert m.reader("groupby_fallbacks")(_obs(**d["families"])) == 0.0
    assert m.reader("groupby_lane_share")(_obs(**d["families"])) == 100.0
