"""CPU tests of the two per-layer readers PR 40 added: `host_syncs` (the
window's delta of `syncs` in `obs.dispatch.counters()`, over its queries)
and `host_sync_ms` (phase `host-sync`, per query), on hand-made
observations: silent for a program older than them, 0.0 a reading, the
value otherwise; and their entries at the end of `per_layer`. The traced
rehearsals of the cells find both on their result lines
(`test_benchmark_harness.py`). None of this is a chip run."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import observe  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

NEW = ("host_syncs", "host_sync_ms")


def _obs(queries=4, counters=None, phases=None):
    return observe.Observation(
        queries=queries, window_s=1.0,
        window={"counters": counters if counters is not None else {},
                "phases": phases if phases is not None else {}},
        setup={}, trace=None, work={}, peaks={}, memory_peak_bytes=0)


# a program older than PR 40: the ledger has no `syncs`, the phase ledger no
# `host-sync`; and a window in which no query completed
OLDER = {"counters": {"dispatches": 40, "traces": 0},
         "phases": {"device-wait": 8_000_000, "other": 2_000_000}}
NOW = {"counters": {"dispatches": 40, "traces": 0, "syncs": 12,
                    "sync_ns": 9_000_000},
       "phases": {"device-wait": 8_000_000, "host-sync": 6_000_000}}
NONE_READ = {"counters": {"dispatches": 40, "syncs": 0, "sync_ns": 0},
             "phases": {"device-wait": 8_000_000, "host-sync": 0}}


@pytest.mark.parametrize("metric", NEW)
def test_a_program_older_than_the_counter_and_phase_reads_as_nothing(metric):
    read = Manifest().reader(metric)
    assert read(_obs(**OLDER)) is None
    assert read(_obs(queries=0, **NOW)) is None


@pytest.mark.parametrize("metric", NEW)
def test_nought_is_a_reading(metric):
    assert Manifest().reader(metric)(_obs(**NONE_READ)) == 0.0


@pytest.mark.parametrize("metric,want", [("host_syncs", 3.0),
                                         ("host_sync_ms", 1.5)])
def test_the_window_over_its_queries(metric, want):
    assert Manifest().reader(metric)(_obs(**NOW)) == pytest.approx(want)


def test_a_snapshot_carries_the_sync_counter_and_phase_and_delta_reads_them():
    """`lib/observe.snapshot()` already deltas the two surfaces the readers
    read: no benchmark file needed an edit."""
    from spark_rapids_tpu.obs import dispatch, phase
    import jax.numpy as jnp
    a = observe.snapshot()
    int(dispatch.host_read("fixture.read", jnp.arange(3).sum()))
    d = observe.delta(a, observe.snapshot())
    assert d["counters"]["syncs"] == 1 and d["counters"]["sync_ns"] > 0
    assert "host-sync" in d["phases"] and "host-sync" in phase.counters()


@pytest.mark.parametrize("metric", NEW)
def test_the_entries_are_appended_for_every_accepted_cell(doc, metric):
    names = [e["name"] for e in doc["per_layer"]]
    # after the last metric accepted before the two, in their order
    at = names.index("topn_busy_share") + 1
    assert names[at:at + 2] == list(NEW)
    entry = doc["per_layer"][names.index(metric)]
    assert (entry["layer"], entry["moves"], entry["better"]) == \
        ("device", "query_s", "lower")
    assert entry["workloads"][:4] == [
        "q6_scan_filter_sum", "q14_join_like_ratio",
        "q1_groupby_string_keys", "q3_join_groupby_topn"]
