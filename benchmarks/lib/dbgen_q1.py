"""TPC-H's population rules (specification v3.0.1, clause 4.2.3) for the
lineitem columns Q1 reads, beside `dbgen.py` (which no later PR edits, and
whose `lineitems()` returns neither the tax nor the two flags). The
dependencies between columns are the specification's and `dbgen.py`'s: order
dates, lines an order and the retail price come from there. numpy only."""

import numpy as np

from . import dbgen

#: CURRENTDATE (clause 4.2.2.12): 1995-06-17, in days since 1970-01-01
CURRENTDATE = 9298


def lineitems_with_flags(rng, n_lines: int, scale_factor: float) -> dict:
    """`dbgen.lineitems` with the columns Q1 adds: `n_lines` rows in order
    of their orders, 1 to 7 lines an order; L_QUANTITY in [1..50];
    L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE of a part drawn from the
    200,000 x SF; L_DISCOUNT in [0.00..0.10]; L_TAX in [0.00..0.08];
    L_SHIPDATE = O_ORDERDATE + [1..121]; L_RECEIPTDATE = L_SHIPDATE +
    [1..30]; L_RETURNFLAG "R" or "A" at random where the receipt date is not
    after CURRENTDATE, else "N"; L_LINESTATUS "O" where the ship date is
    after CURRENTDATE, else "F". The two flags are numpy object arrays of
    `str`, which `pyarrow.array` takes as string columns."""
    odate = dbgen.order_dates(rng, scale_factor)
    odate = np.repeat(odate, dbgen.lines_per_order(rng, len(odate), n_lines))
    qty = rng.integers(1, 51, n_lines)
    partkey = rng.integers(1, int(round(200_000 * scale_factor)) + 1, n_lines)
    ship = (odate + rng.integers(1, 122, n_lines)).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n_lines).astype(np.int32)
    returned = np.array(["R", "A"], dtype=object)[rng.integers(0, 2, n_lines)]
    flag = np.where(receipt <= CURRENTDATE, returned, "N").astype(object)
    status = np.where(ship > CURRENTDATE, "O", "F").astype(object)
    return {
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": (qty * dbgen.retail_price_cents(partkey)) / 100.0,
        "l_discount": rng.integers(0, 11, n_lines) / 100.0,
        "l_tax": rng.integers(0, 9, n_lines) / 100.0,
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_shipdate": ship,
        "l_receiptdate": receipt,
    }
