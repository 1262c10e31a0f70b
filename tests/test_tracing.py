"""Tracing surface (reference NVTX integration): operator annotations
must not perturb results. The span primitive and its sites are tested in
tests/test_spans.py."""

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.types import LONG, Schema, StructField


def _df(sess):
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    return sess.from_pydict({"k": [1, 1, 2], "v": [10, 20, 30]}, sch)


def test_annotations_wrap_execution():
    # annotation must not perturb results
    sess = TpuSession()
    got = sorted(_df(sess).group_by("k").agg((F.sum("v"), "s")).collect())
    assert got == [(1, 30), (2, 30)]
