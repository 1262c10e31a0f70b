"""tpch-q1: seeded data by TPC-H's population rules, the plain reference of
Q1 as published, the comparison and the work model. Imports nothing of the
program under test; numpy only."""

import datetime
import math

import numpy as np

from benchmarks.lib import dbgen_q1

EPOCH = datetime.date(1970, 1, 1).toordinal()
WIDTHS = {"DOUBLE": 8, "DATE": 4, "STRING": 1}      # CHAR(1) as stored
KEYS = ("l_returnflag", "l_linestatus")
#: the result's columns after the two keys: seven DOUBLE, then the count
DOUBLES = 7


def cutoff(cfg: dict) -> int:
    """date '1998-12-01' - DELTA days, as days since 1970."""
    last = datetime.date(1998, 12, 1).toordinal() - EPOCH
    return last - int(cfg["params"]["delta_days"])


def generate(seed: int, cfg: dict) -> dict:
    """{table: {column: array}} from the seed alone: the seven columns of
    lineitem that Q1 reads."""
    sf = float(cfg["scale"]["scale_factor"])
    rng = np.random.default_rng([seed, 1])
    line = dbgen_q1.lineitems_with_flags(
        rng, int(cfg["scale"]["lineitem_rows"]), sf)
    return {"lineitem": {k: line[k] for k in cfg["schema"]["lineitem"]}}


def _groups(line: dict, keep: np.ndarray) -> tuple:
    """(keys, group of each kept row): the distinct (l_returnflag,
    l_linestatus) pairs among the kept rows in the ORDER BY's order (UTF-8
    binary order, which is numpy's code-point order; a NULL key before
    every value, as Spark sorts ascending), and each kept row's index into
    them."""
    codes, values = [], []
    for k in KEYS:
        col = np.asarray(line[k], dtype=object)[keep]
        null = np.equal(col, None)
        vals, inv = np.unique(np.where(null, "", col).astype(str),
                              return_inverse=True)
        codes.append(np.where(null, 0, inv + 1))
        values.append([None] + vals.tolist())
    span = len(values[1])
    pair, group = np.unique(codes[0] * span + codes[1], return_inverse=True)
    keys = [(values[0][p // span], values[1][p % span]) for p in pair]
    return keys, group


def reference(tables: dict, cfg: dict, dtype=np.float64) -> list:
    """Q1's rows in key order: (l_returnflag, l_linestatus, sum_qty,
    sum_base_price, sum_disc_price, sum_charge, avg_qty, avg_price,
    avg_disc, count_order). `dtype` is the precision of the DOUBLE
    arithmetic: float64 as the configuration states, float32 for the
    control. The predicate and the keys are on the stored values, whatever
    `dtype`; an average is its sum over its count; the count is an integer."""
    line = tables["lineitem"]
    keep = line["l_shipdate"] <= cutoff(cfg)
    keys, group = _groups(line, keep)
    qty, price, disc, tax = (line[k][keep].astype(dtype) for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (dtype(1.0) - disc)
    charge = disc_price * (dtype(1.0) + tax)
    rows = []
    for g, key in enumerate(keys):
        m = group == g
        n = int(m.sum())
        sums = [x[m].sum(dtype=dtype) for x in (qty, price, disc_price,
                                                charge)]
        avgs = [x[m].sum(dtype=dtype) / dtype(n) for x in (qty, price, disc)]
        rows.append(key + tuple(float(v) for v in sums + avgs) + (n,))
    return rows


def as_rows(answer: list) -> list:
    """The reference's answer in the shape `collect()` returns."""
    return list(answer)


def compare(rows: list, answer: list) -> dict:
    """{number: value} of one query's rows against the reference.
    `rows_wrong`: rows too many or too few, and of the rows both have, by
    position, each whose key strings or `count_order` differ or that holds
    a NULL where the reference has a value. `sum_rel_err`: the LARGEST
    relative error over the seven DOUBLE columns of every row both have (a
    value that is not finite is over any limit)."""
    wrong = abs(len(rows) - len(answer))
    worst = 0.0
    for got, ref in zip(rows, answer):
        if len(got) != len(ref) or tuple(got[:2]) != tuple(ref[:2]) \
                or got[-1] != ref[-1] \
                or any(v is None for v in got[2:]):
            wrong += 1
            continue
        for v, r in zip(got[2:2 + DOUBLES], ref[2:2 + DOUBLES]):
            v = float(v)
            err = abs(v - r) / (abs(r) or 1.0) if math.isfinite(v) \
                else math.inf
            worst = max(worst, err)
    return {"rows_wrong": wrong, "sum_rel_err": worst}


def work_model(cfg: dict, tables: dict) -> dict:
    """Bytes the QUERY needs the group-by's update to touch per query: every
    row's seven columns once at their stored width (four DOUBLE, the DATE,
    two CHAR(1) keys: 38 B) and the result (a row a group: two key bytes,
    seven DOUBLE, one BIGINT). Not the program's decoded strings, offsets,
    hashes, buckets or padding. Memory-bound: one compare, two multiplies,
    two adds, a hash and seven accumulations a row."""
    line = tables["lineitem"]
    rows = len(line["l_shipdate"])
    row_bytes = sum(WIDTHS[t] for t in cfg["schema"]["lineitem"].values())
    keys, _ = _groups(line, line["l_shipdate"] <= cutoff(cfg))
    result = len(keys) * (2 * WIDTHS["STRING"] + DOUBLES * 8 + 8)
    return {"groupby_update": {"bytes": rows * row_bytes + result,
                               "bound": "memory"}}
