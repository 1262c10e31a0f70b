"""The next PR's configuration as `test_benchmark_harness.appended_copy`
writes it: TPC-H Q3 over a lineitem that has no `l_shipdate`, so its
lineitem predicate is gone and every line joins. The data, reference,
comparison and work model are `tpch-q3`'s (loaded from its file beside this
one in the copy); numpy only."""

import importlib.util
import os

import numpy as np

_Q3 = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tpch-q3", "reference.py")
_spec = importlib.util.spec_from_file_location("bench_next_q3ref", _Q3)
q3 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(q3)

# `tpch-q3`'s generator keeps the columns the schema names: no ship date
generate, as_rows, compare, work_model = (q3.generate, q3.as_rows,
                                          q3.compare, q3.work_model)


def reference(tables: dict, cfg: dict, dtype=np.float64) -> dict:
    """Q3's answer where every line passes the lineitem predicate: each
    line is given a ship date after any DATE."""
    line = tables["lineitem"]
    shipped = np.full(len(line["l_orderkey"]), np.iinfo(np.int32).max)
    return q3.reference({**tables, "lineitem": {**line,
                                                "l_shipdate": shipped}},
                        cfg, dtype)
