"""TPC-H's population rules (specification v3.0.1, clauses 4.2.2.13 and
4.2.3) for `customer`, `orders` and the lineitem columns Q3 reads, beside
`dbgen.py` (which no later PR edits, and whose `lineitems()` returns no
order key). The dependencies between columns are the specification's and
`dbgen.py`'s: order dates, lines an order and the retail price come from
there. numpy only."""

import numpy as np

from . import dbgen

#: C_MKTSEGMENT's five values (clause 4.2.2.13, "Segments")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")


def customers(rng, n: int) -> dict:
    """`n` customers: C_CUSTKEY dense in 1..n in key order; C_MKTSEGMENT one
    of the five segments, drawn uniformly. `c_mktsegment` is a numpy object
    array of `str`, which `pyarrow.array` takes as a string column."""
    seg = np.array(SEGMENTS, dtype=object)[rng.integers(0, len(SEGMENTS), n)]
    return {"c_custkey": np.arange(1, n + 1, dtype=np.int64),
            "c_mktsegment": seg}


def order_keys(n_orders: int) -> np.ndarray:
    """O_ORDERKEY is sparse: of every 32 consecutive keys only the first 8
    are used (clause 4.2.3), in key order from 1."""
    i = np.arange(n_orders, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def orders_and_lineitems(rng, n_lines: int, n_customers: int,
                         scale_factor: float) -> tuple:
    """(orders, lineitem): 1,500,000 x SF orders in key order and `n_lines`
    lines in order of their orders, 1 to 7 lines an order. O_ORDERKEY
    sparse (`order_keys`); O_CUSTKEY uniform over the customers whose key is
    not divisible by 3 (a third of the customers have no order);
    O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151 days]; O_SHIPPRIORITY
    0; L_ORDERKEY its order's key; L_QUANTITY in [1..50]; L_EXTENDEDPRICE =
    L_QUANTITY * P_RETAILPRICE of a part drawn from the 200,000 x SF;
    L_DISCOUNT in [0.00..0.10]; L_SHIPDATE = O_ORDERDATE + [1..121]."""
    odate = dbgen.order_dates(rng, scale_factor)
    n_orders = len(odate)
    per_order = dbgen.lines_per_order(rng, n_orders, n_lines)
    okey = order_keys(n_orders)
    # the k-th key not divisible by 3 is k + (k - 1) // 2: 1, 2, 4, 5, 7, ...
    k = rng.integers(1, n_customers - n_customers // 3 + 1, n_orders)
    qty = rng.integers(1, 51, n_lines)
    partkey = rng.integers(1, int(round(200_000 * scale_factor)) + 1, n_lines)
    ship = np.repeat(odate, per_order) + rng.integers(1, 122, n_lines)
    orders = {
        "o_orderkey": okey,
        "o_custkey": (k + (k - 1) // 2).astype(np.int64),
        "o_orderdate": odate.astype(np.int32),
        "o_shippriority": np.zeros(n_orders, dtype=np.int32),
    }
    lineitem = {
        "l_orderkey": np.repeat(okey, per_order),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": (qty * dbgen.retail_price_cents(partkey)) / 100.0,
        "l_discount": rng.integers(0, 11, n_lines) / 100.0,
        "l_shipdate": ship.astype(np.int32),
    }
    return orders, lineitem
