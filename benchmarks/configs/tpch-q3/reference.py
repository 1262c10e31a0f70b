"""tpch-q3: seeded data by TPC-H's population rules, the plain reference of
Q3 as published, the comparison and the work model. Imports nothing of the
program under test; numpy only."""

import datetime
import math

import numpy as np

from benchmarks.lib import dbgen_q3

EPOCH = datetime.date(1970, 1, 1).toordinal()
WIDTHS = {"BIGINT": 8, "DOUBLE": 8, "DATE": 4, "INT": 4}
#: the rows Q3 returns, and how many of the reference's the comparison looks
#: at: one more, so that a tie between the 10th and the 11th is seen
TOP = 10
LOOKED_AT = TOP + 1


def cut_date(cfg: dict) -> int:
    """DATE as days since 1970."""
    return datetime.date.fromisoformat(cfg["params"]["date"]).toordinal() \
        - EPOCH


def generate(seed: int, cfg: dict) -> dict:
    """{table: {column: array}} from the seed alone: the columns of
    lineitem, orders and customer that Q3 reads."""
    scale = cfg["scale"]
    rng = np.random.default_rng([seed, 3])
    cust = dbgen_q3.customers(rng, int(scale["customer_rows"]))
    orders, line = dbgen_q3.orders_and_lineitems(
        rng, int(scale["lineitem_rows"]), int(scale["customer_rows"]),
        float(scale["scale_factor"]))
    made = {"lineitem": line, "orders": orders, "customer": cust}
    return {t: {k: made[t][k] for k in cols}
            for t, cols in cfg["schema"].items()}


def _lookup(keys: np.ndarray, wanted: np.ndarray) -> tuple:
    """(met, at): for each of `wanted` whether `keys` holds it and, where it
    does, the index in `keys` of one that equals it."""
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys[order], wanted)
    met = at < len(keys)
    met[met] = keys[order][at[met]] == wanted[met]
    return met, order[np.where(met, at, 0)]


def joined(tables: dict, cfg: dict) -> dict:
    """The rows Q3 groups: each lineitem row shipped after DATE with its
    order, where that order was placed before DATE by a customer of
    SEGMENT (inner joins: a row without its order, an order without its
    customer, is dropped). The predicates are on the stored values."""
    line, orders, cust = (tables[t] for t in ("lineitem", "orders",
                                              "customer"))
    date = cut_date(cfg)
    seg = np.asarray(cust["c_mktsegment"], dtype=object) \
        == cfg["params"]["segment"]
    o_keep = np.flatnonzero(orders["o_orderdate"] < date)
    has_cust, _ = _lookup(np.asarray(cust["c_custkey"])[seg],
                          orders["o_custkey"][o_keep])
    o_keep = o_keep[has_cust]
    l_keep = np.flatnonzero(line["l_shipdate"] > date)
    met, at = _lookup(orders["o_orderkey"][o_keep],
                      line["l_orderkey"][l_keep])
    l_keep, o_at = l_keep[met], o_keep[at[met]]
    return {"l_orderkey": line["l_orderkey"][l_keep],
            "o_orderdate": orders["o_orderdate"][o_at],
            "o_shippriority": orders["o_shippriority"][o_at],
            "l_extendedprice": line["l_extendedprice"][l_keep],
            "l_discount": line["l_discount"][l_keep]}


def reference(tables: dict, cfg: dict, dtype=np.float64) -> dict:
    """Q3's first rows, one more than it returns: {"rows": [(l_orderkey,
    revenue, o_orderdate, o_shippriority)] in the ORDER BY's order (revenue
    descending, then o_orderdate; then l_orderkey, which the query leaves
    open and no two rows here need), "groups", "joined_rows", "near": the
    relative distance under which two revenues count as tied}. `dtype` is
    the precision of the DOUBLE arithmetic: float64 as the configuration
    states, float32 for the control. The keys are the stored values,
    whatever `dtype`; a date is days since 1970."""
    j = joined(tables, cfg)
    keys = np.stack([j["l_orderkey"], j["o_orderdate"].astype(np.int64),
                     j["o_shippriority"].astype(np.int64)], axis=1)
    groups, of_row = np.unique(keys, axis=0, return_inverse=True) \
        if len(keys) else (keys, np.zeros(0, np.int64))
    rev = j["l_extendedprice"].astype(dtype) \
        * (dtype(1.0) - j["l_discount"].astype(dtype))
    # a group's rows in their stored order, summed in `dtype`
    by_group = np.argsort(of_row.ravel(), kind="stable")
    starts = np.searchsorted(of_row.ravel()[by_group], np.arange(len(groups)))
    revenue = np.add.reduceat(rev[by_group], starts, dtype=dtype) \
        if len(groups) else np.zeros(0, dtype)
    first = np.lexsort((groups[:, 0], groups[:, 1],
                        -revenue.astype(np.float64)))[:LOOKED_AT]
    rows = [(int(groups[g, 0]), float(revenue[g]), int(groups[g, 1]),
             int(groups[g, 2])) for g in first]
    return {"rows": rows, "groups": len(groups), "joined_rows": len(keys),
            "near": float(cfg["limits"]["sum_rel_err"])}


def as_rows(answer: dict) -> list:
    """The reference's answer in the shape `collect()` returns: ten rows,
    the date as days since 1970."""
    return list(answer["rows"][:TOP])


def tied_runs(answer: dict) -> list:
    """For each of the reference's rows the (first, last) positions of the
    run of neighbours it is tied with: two neighbouring revenues within
    `near` of each other, relatively, are tied (the chip holds a DOUBLE as a
    pair of float32, so it cannot be asked to order them), and ties chain."""
    rev = [r[1] for r in answer["rows"]]
    runs, start = [], 0
    for i in range(1, len(rev) + 1):
        if i == len(rev) or abs(rev[i] - rev[i - 1]) > \
                answer["near"] * max(abs(rev[i]), abs(rev[i - 1])):
            runs += [(start, i - 1)] * (i - start)
            start = i
    return runs


def smallest_gap(answer: dict) -> float:
    """The smallest relative distance between two neighbouring revenues of
    the reference's rows (what `near` is compared with); inf with fewer
    than two rows."""
    rev = [r[1] for r in answer["rows"]]
    return min((abs(a - b) / max(abs(a), abs(b), 1e-300)
                for a, b in zip(rev, rev[1:])), default=math.inf)


def compare(rows: list, answer: dict) -> dict:
    """{number: value} of one query's rows against the reference, by
    position. `rows_wrong`: rows too many or too few, and each row whose
    three keys are not exactly those of the reference's row at its position
    or that holds a NULL. Where the reference's row is tied with
    neighbours (`tied_runs`), the keys of any row of its run not yet met
    are right: either order of two tied rows, either of a tied 10th and
    11th. `near_ties`: how many of the ten positions were judged by that
    rule. `sum_rel_err`: the largest relative error of `revenue` over the
    rows whose keys were right (a value that is not finite is over any
    limit)."""
    ref = answer["rows"]
    want = min(TOP, len(ref))
    runs = tied_runs(answer)
    wrong = abs(len(rows) - want)
    worst, near_ties, met = 0.0, 0, set()
    for i, got in enumerate(rows[:want]):
        if len(got) != 4 or any(v is None for v in got):
            wrong += 1
            continue
        key = (int(got[0]), int(got[2]), int(got[3]))
        lo, hi = runs[i]
        near_ties += hi > lo
        at = next((j for j in range(lo, hi + 1) if j not in met
                   and (ref[j][0], ref[j][2], ref[j][3]) == key), None)
        if at is None:
            wrong += 1
            continue
        met.add(at)
        v, r = float(got[1]), ref[at][1]
        worst = max(worst, abs(v - r) / (abs(r) or 1.0)
                    if math.isfinite(v) else math.inf)
    return {"rows_wrong": wrong, "near_ties": near_ties,
            "sum_rel_err": worst}


def work_model(cfg: dict, tables: dict) -> dict:
    """Bytes the QUERY needs touched per query: every lineitem row's four
    columns once (28 B), every order's four (24 B), every customer's key
    and its `c_mktsegment` as stored (the bytes of the strings), and ten
    result rows. Not the program's tables, hashes, sorted copies or
    padding. Memory-bound: two lookups, three compares, a multiply and an
    add a row, a sort of the groups. One entry: `query_hbm_share` sums the
    entries, so the same bytes under `join_probe` too would count twice."""
    schema = cfg["schema"]
    total = 0
    for table in ("lineitem", "orders"):
        rows = len(next(iter(tables[table].values())))
        total += rows * sum(WIDTHS[t] for t in schema[table].values())
    cust = tables["customer"]
    total += len(cust["c_custkey"]) * WIDTHS["BIGINT"] \
        + sum(len(s.encode()) for s in cust["c_mktsegment"])
    total += TOP * (WIDTHS["BIGINT"] + WIDTHS["DOUBLE"] + WIDTHS["DATE"]
                    + WIDTHS["INT"])
    return {"join_groupby": {"bytes": total, "bound": "memory"}}
