"""tpch-q6: seeded data by TPC-H's population rules, the plain reference of
Q6 as published, the comparison and the work model. Imports nothing of the
program under test; numpy only."""

import datetime
import math

import numpy as np

from benchmarks.lib import dbgen

EPOCH = datetime.date(1970, 1, 1).toordinal()


def date_range(cfg: dict) -> tuple:
    """[DATE, DATE + 1 year) as days since 1970."""
    d = datetime.date.fromisoformat(cfg["params"]["date"])
    return (d.toordinal() - EPOCH,
            d.replace(year=d.year + 1).toordinal() - EPOCH)


def generate(seed: int, cfg: dict) -> dict:
    """{table: {column: array}} from the seed alone: the four columns of
    lineitem that Q6 reads."""
    sf = float(cfg["scale"]["scale_factor"])
    rng = np.random.default_rng([seed, 6])
    line = dbgen.lineitems(rng, int(cfg["scale"]["lineitem_rows"]), sf)
    return {"lineitem": {k: line[k] for k in cfg["schema"]["lineitem"]}}


def reference(tables: dict, cfg: dict, dtype=np.float64) -> list:
    """Q6's one row: (revenue,). `dtype` is the precision of the DOUBLE
    arithmetic: float64 as the configuration states, float32 for the
    control. The predicates are on the stored values, whatever `dtype`."""
    p = cfg["params"]
    d = tables["lineitem"]
    lo, hi = date_range(cfg)
    keep = ((d["l_shipdate"] >= lo) & (d["l_shipdate"] < hi)
            & (d["l_discount"] >= p["discount_min"])
            & (d["l_discount"] <= p["discount_max"])
            & (d["l_quantity"] < p["quantity_below"]))
    rev = d["l_extendedprice"][keep].astype(dtype) \
        * d["l_discount"][keep].astype(dtype)
    return [(float(rev.sum(dtype=dtype)),)]


def as_rows(answer: list) -> list:
    """The reference's answer in the shape `collect()` returns."""
    return list(answer)


def compare(rows: list, answer: list) -> dict:
    """{number: value} of one query's rows against the reference: a count of
    rows other than one, and revenue's relative error. A value that is not
    finite is over any limit."""
    if len(rows) != 1 or len(rows[0]) != 1 or rows[0][0] is None:
        return {"rows_wrong": 1, "sum_rel_err": 0.0}
    got, ref = float(rows[0][0]), answer[0][0]
    return {"rows_wrong": 0,
            "sum_rel_err": abs(got - ref) / abs(ref)
            if math.isfinite(got) else math.inf}


def work_model(cfg: dict, tables: dict) -> dict:
    """Bytes the QUERY needs the filter + aggregate stage to touch per
    query: every row's four columns once (DOUBLE 8, DATE 4) and one result
    row. Not the program's buffers, copies or padding. Memory-bound: four
    compares, one multiply and one add a row."""
    rows = len(tables["lineitem"]["l_shipdate"])
    widths = {"DOUBLE": 8, "DATE": 4}
    row_bytes = sum(widths[t] for t in cfg["schema"]["lineitem"].values())
    return {"agg_stage": {"bytes": rows * row_bytes + 8, "bound": "memory"}}
