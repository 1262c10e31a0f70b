"""CPU tests of the per-layer readers that PR 27 added beside the others:
four that read the phases the engine's spans write, two that join the
device trace's XLA module names to the dispatch ledger's labels."""

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import harness, trace  # noqa: E402
from benchmarks.lib.manifest import Manifest, manifest_at  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixture_q1_tiny_v5e.xplane.pb.gz"
CELL = "q6_scan_filter_sum"
SPAN_METRICS = {"plan_span_ms": "plan", "scan_decode_ms": "scan-decode",
                "upload_ms": "upload", "device_wait_ms": "device-wait"}
# the recorded trace's own numbers (`tools/trace_look.py` prints them)
BUSY_S = 0.025168891
CONCAT_S, UNPACK_S = 0.024301018, 0.000637871
AGG_S, PACK_S = 0.000248021, 1.3459e-05
LABELS = {"jit__concat_pair": ["coalesce.concat_pair"],
          "jit__unpack_batch_impl": ["upload.unpack_batch"],
          "jit__agg_spec_body": ["CompiledStageExec.step"],
          "jit__pack_impl": ["transfer.pack_batch"],
          "jit__never_ran": ["upload.unpack_leaves"]}


class Obs:
    """A hand-made observation: what `lib/observe.Observation` carries."""

    def __init__(self, queries=2, phases=None, trace=None):
        self.queries = queries
        self.window = {"phases": phases or {}}
        self.trace = trace


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    raw = tmp_path_factory.mktemp("fixture") / "fixture.xplane.pb"
    raw.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return trace.reduce(trace.load(str(raw)))


@pytest.fixture
def module_labels(monkeypatch):
    """Put a map in the place of the program's `module_labels()`."""
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


# -- the readers of the spans' phases ------------------------------------------

@pytest.mark.parametrize("metric,phase", sorted(SPAN_METRICS.items()))
def test_a_span_reader_gives_the_phases_milliseconds_a_query(metric, phase):
    read = Manifest().reader(metric)
    others = {p: 7_000_000 for p in SPAN_METRICS.values() if p != phase}
    assert read(Obs(2, {**others, phase: 3_000_000})) == pytest.approx(1.5)
    # a time of nothing is a reading; a phase the program lacks is not
    assert read(Obs(2, {**others, phase: 0})) == 0.0
    assert read(Obs(2, others)) is None
    assert read(Obs(0, {phase: 3_000_000})) is None


# -- the readers that join the trace to the ledger's labels --------------------

@pytest.mark.parametrize("metric,want", [
    ("ingest_busy_share", 100 * (CONCAT_S + UNPACK_S) / BUSY_S),
    ("labelled_busy_share",
     100 * (CONCAT_S + UNPACK_S + AGG_S + PACK_S) / BUSY_S),
])
def test_label_readers_on_the_recorded_chip_trace(reduced, module_labels,
                                                  metric, want):
    read = Manifest().reader(metric)
    module_labels(LABELS)
    assert read(Obs(trace=reduced)) == pytest.approx(want, rel=1e-6)
    assert read(Obs(trace=None)) is None              # no trace: silent


def test_eager_device_work_is_what_labelled_busy_share_leaves_out(
        reduced, module_labels):
    """The recorded trace holds two programs that went past `instrument`
    (`jit_convert_element_type`, `jit_broadcast_in_dim`): no label, so no
    reader counts them, and with every program labelled the share reads
    the sum of the module events over busy (a module's event spans the
    gaps between its ops, so that sum can pass busy by a little)."""
    read = Manifest().reader("labelled_busy_share")
    everything = {m: ["x.y"] for m in reduced.module_s}
    module_labels(everything)
    whole = read(Obs(trace=reduced))
    module_labels(LABELS)
    assert whole - read(Obs(trace=reduced)) == pytest.approx(
        100 * (1.2344e-05 + 9.116e-06) / BUSY_S, rel=1e-3)
    assert whole == pytest.approx(100 * sum(reduced.module_s.values())
                                  / BUSY_S)


def test_a_module_with_labels_on_both_sides_silences_the_ingest_share(
        reduced, module_labels):
    module_labels({**LABELS, "jit__agg_spec_body":
                   ["CompiledStageExec.step", "coalesce.step"]})
    assert Manifest().reader("ingest_busy_share")(Obs(trace=reduced)) is None
    # what is labelled does not depend on the side
    assert Manifest().reader("labelled_busy_share")(Obs(trace=reduced)) > 99
    # an ambiguous module that did not run in the window harms nothing
    module_labels({**LABELS, "jit__never_ran": ["upload.a", "Sort.b"]})
    assert Manifest().reader("ingest_busy_share")(Obs(trace=reduced)) > 99


@pytest.mark.parametrize("metric", ["ingest_busy_share",
                                    "labelled_busy_share"])
def test_label_readers_are_silent_where_nothing_can_be_read(
        reduced, module_labels, monkeypatch, metric):
    read = Manifest().reader(metric)
    module_labels({})                                 # the ledger is off
    assert read(Obs(trace=reduced)) is None
    module_labels({"jit__other": ["Sort.sort"]})      # no share reads 0
    assert read(Obs(trace=reduced)) is None
    # a program from before PR 27 has no `module_labels` at all
    from spark_rapids_tpu.obs import dispatch
    monkeypatch.delattr(dispatch, "module_labels")
    assert read(Obs(trace=reduced)) is None


# -- through the harness -------------------------------------------------------

def test_the_traced_rehearsal_reports_the_four_span_metrics(tmp_path):
    # a root of its own: another worker rehearses this cell in the checkout
    res = harness.run_cell(CELL, 2147483659, 0.5, True, require_tpu=False,
                           rehearse=True, manifest=manifest_at(tmp_path))
    assert res["correct"] is True
    for name in SPAN_METRICS:
        assert res["metrics"][name]["unit"] == "ms/query"
        assert res["metrics"][name]["value"] > 0, name
    # on the CPU there is no device plane: the two shares stay silent
    assert "ingest_busy_share" not in res["metrics"]
    assert "labelled_busy_share" not in res["metrics"]
    # the spans cost nothing the older readers can see
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["metrics"]["ingest_stall_ms"]["value"] >= 0
