"""tpch-q14: seeded data by TPC-H's population rules, the plain reference of
Q14 as published, the comparison and the work model. Imports nothing of the
program under test; numpy only."""

import datetime
import math

import numpy as np

from benchmarks.lib import dbgen_part

EPOCH = datetime.date(1970, 1, 1).toordinal()
WIDTHS = {"BIGINT": 8, "DOUBLE": 8, "DATE": 4}


def date_range(cfg: dict) -> tuple:
    """[DATE, DATE + 1 month) as days since 1970."""
    d = datetime.date.fromisoformat(cfg["params"]["date"])
    end = d.replace(year=d.year + d.month // 12, month=d.month % 12 + 1)
    return d.toordinal() - EPOCH, end.toordinal() - EPOCH


def generate(seed: int, cfg: dict) -> dict:
    """{table: {column: array}} from the seed alone: the four columns of
    lineitem and the two of part that Q14 reads."""
    sf = float(cfg["scale"]["scale_factor"])
    rng = np.random.default_rng([seed, 14])
    line = dbgen_part.lineitems_with_partkey(
        rng, int(cfg["scale"]["lineitem_rows"]), sf)
    part = dbgen_part.parts(rng, int(cfg["scale"]["part_rows"]))
    return {"lineitem": {k: line[k] for k in cfg["schema"]["lineitem"]},
            "part": {k: part[k] for k in cfg["schema"]["part"]}}


def reference(tables: dict, cfg: dict, dtype=np.float64) -> list:
    """Q14's one row: (promo_revenue,). Each lineitem row of the month looks
    its part up by key (a row without a part is dropped: an inner join);
    `dtype` is the precision of the DOUBLE arithmetic: float64 as the
    configuration states, float32 for the control. The predicates are on
    the stored values, whatever `dtype`. A month without rows, or without
    revenue, answers NULL: SQL's sum over no rows is NULL, and Spark's
    x / 0 is NULL."""
    line, part = tables["lineitem"], tables["part"]
    lo, hi = date_range(cfg)
    month = (line["l_shipdate"] >= lo) & (line["l_shipdate"] < hi)
    pkey = np.asarray(part["p_partkey"])
    order = np.argsort(pkey, kind="stable")
    lkey = line["l_partkey"][month]
    at = np.searchsorted(pkey[order], lkey)
    met = at < len(pkey)
    met[met] = pkey[order][at[met]] == lkey[met]
    ptype = np.asarray(part["p_type"], dtype=str)[order][at[met]]
    promo = np.char.startswith(ptype, cfg["params"]["type_prefix"])
    rev = line["l_extendedprice"][month][met].astype(dtype) \
        * (dtype(1.0) - line["l_discount"][month][met].astype(dtype))
    total = rev.sum(dtype=dtype)
    if not len(rev) or total == 0:
        return [(None,)]
    ratio = dtype(100.0) * np.where(promo, rev, dtype(0.0)).sum(dtype=dtype) \
        / total
    return [(float(ratio),)]


def as_rows(answer: list) -> list:
    """The reference's answer in the shape `collect()` returns."""
    return list(answer)


def compare(rows: list, answer: list) -> dict:
    """{number: value} of one query's rows against the reference: a count of
    rows other than one, and `promo_revenue`'s relative error (its absolute
    error where the reference is 0.0: no part of the month is PROMO). The
    SQL answer of 0/0 (an empty month) is NULL, and only NULL equals it. A
    value that is not finite is over any limit."""
    if len(rows) != 1 or len(rows[0]) != 1:
        return {"rows_wrong": 1, "sum_rel_err": 0.0}
    got, ref = rows[0][0], answer[0][0]
    if got is None or ref is None:
        return {"rows_wrong": int(got is not ref), "sum_rel_err": 0.0}
    got = float(got)
    return {"rows_wrong": 0,
            "sum_rel_err": abs(got - ref) / (abs(ref) or 1.0)
            if math.isfinite(got) else math.inf}


def work_model(cfg: dict, tables: dict) -> dict:
    """Bytes the QUERY needs the join and its two sums to touch per query:
    every lineitem row's four columns once, every part's key once and its
    `p_type` as stored (the bytes of the strings), and one result. Not the
    program's tables, hashes, copies or padding. Memory-bound: one lookup,
    two compares, a prefix match, a multiply and two adds a row."""
    rows = len(tables["lineitem"]["l_shipdate"])
    row_bytes = sum(WIDTHS[t] for t in cfg["schema"]["lineitem"].values())
    part = tables["part"]
    type_bytes = sum(len(s.encode()) for s in part["p_type"])
    return {"join_probe": {
        "bytes": rows * row_bytes + len(part["p_partkey"]) * WIDTHS["BIGINT"]
        + type_bytes + 8,
        "bound": "memory"}}
