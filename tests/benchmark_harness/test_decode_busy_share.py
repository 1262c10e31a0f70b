"""CPU tests of the per-layer metric `decode_busy_share` (ISSUE 32): the
share of the chip's busy time that the dictionary decode of a string column
takes, read from a reduced trace through the program's map from XLA module
to ledger label. None of this is a chip run."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import trace  # noqa: E402
from benchmarks.lib.manifest import Manifest  # noqa: E402

CELL = "q14_join_like_ratio"
PROBE, DECODE, SIZE = "jit__ja_spec_body", "jit__decode", "jit__decoded_bytes"
LABELS = {PROBE: ["CompiledStageExec.probe_step"],
          DECODE: ["encoded.decode"],
          SIZE: ["encoded.decoded_bytes"],
          "jit__concat_pair": ["coalesce.concat_pair"]}


class Obs:
    def __init__(self, trace=None):
        self.trace = trace


def _reduced(module_s, busy_s=2.0):
    return trace.Reduced(1, 3.6, busy_s, dict(module_s),
                         {m: 2 for m in module_s}, [], [])


@pytest.fixture
def module_labels(monkeypatch):
    from spark_rapids_tpu.obs import dispatch

    def stub(labels):
        monkeypatch.setattr(dispatch, "module_labels", lambda: dict(labels),
                            raising=False)
    return stub


@pytest.fixture
def read():
    return Manifest().reader("decode_busy_share")


def test_the_manifest_reports_it_from_the_q14_cell_on(doc):
    """The entry as PR 32 wrote it, wherever it stands; the cell it was added
    for comes first in its list, and later cells with a dictionary decode
    follow (`q1_groupby_string_keys` since PR 37)."""
    entry = dict({e["name"]: e for e in doc["per_layer"]}["decode_busy_share"])
    cells = entry.pop("workloads")
    assert entry == {"name": "decode_busy_share", "unit": "%",
                     "better": "lower", "source": "device_trace",
                     "layer": "kernels", "moves": "query_s"}
    assert cells[0] == CELL and "q6_scan_filter_sum" not in cells


@pytest.mark.parametrize("module_s, want", [
    # PR 31's trace of four queries: the decode 43.9% of busy
    ({PROBE: 3.354, DECODE: 3.041, SIZE: 0.009, "jit__concat_pair": 0.046},
     100 * 3.050 / 6.925),
    # the decode alone, without the program that sizes its bucket
    ({PROBE: 1.2, DECODE: 0.3}, 100 * 0.3 / 6.925),
    # an eager program under no label is nobody's
    ({DECODE: 0.3, "jit_gather": 0.5}, 100 * 0.3 / 6.925),
])
def test_it_reads_the_decodes_programs_by_their_labels(read, module_labels,
                                                       module_s, want):
    module_labels(LABELS)
    assert read(Obs(_reduced(module_s, busy_s=6.925))) == pytest.approx(want)


def test_it_is_silent_where_nothing_can_be_read(read, module_labels,
                                                monkeypatch):
    red = _reduced({PROBE: 1.2, DECODE: 0.3})
    module_labels(LABELS)
    assert read(Obs(None)) is None                           # no trace
    assert read(Obs(_reduced({PROBE: 1.2}))) is None         # no decode ran
    assert read(Obs(_reduced({DECODE: 0.3}, busy_s=0.0))) is None
    module_labels({})                                        # the ledger is off
    assert read(Obs(red)) is None                            # never 0
    # the decode ran eagerly, under no label (before PR 31)
    module_labels({PROBE: LABELS[PROBE]})
    assert read(Obs(_reduced({PROBE: 1.2, "jit_searchsorted": 0.6}))) is None
    # one module serving the decode and something else: cannot be split
    module_labels({**LABELS, DECODE: ["encoded.decode", "Sort.sort"]})
    assert read(Obs(red)) is None
    from spark_rapids_tpu.obs import dispatch
    monkeypatch.delattr(dispatch, "module_labels")           # before PR 27
    assert read(Obs(red)) is None


def test_the_decode_reaches_the_ledger_under_the_labels_it_reads():
    """The labels are the program's, not this file's: a decode through
    `materialize_column` is in `module_labels()` under them."""
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.layer_metrics.decode_busy_share import DECODE_LABELS
    from spark_rapids_tpu.columnar import StringColumn
    from spark_rapids_tpu.columnar.encoded import (
        DictionaryColumn, materialize_column)
    from spark_rapids_tpu.obs import dispatch
    entries = StringColumn.from_pylist(["PROMO TIN", "", "STANDARD"])
    codes = np.array([2, 0, 1, 0], np.int32)
    out = materialize_column(DictionaryColumn(
        jnp.asarray(codes), entries.data, entries.offsets,
        jnp.ones(4, jnp.bool_)))
    assert out.to_pylist(4) == ["STANDARD", "PROMO TIN", "", "PROMO TIN"]
    served = {label for module in ("jit__decode", "jit__decoded_bytes")
              for label in dispatch.module_labels().get(module, ())}
    assert served == DECODE_LABELS
