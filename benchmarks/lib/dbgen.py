"""TPC-H's population rules (specification v3.0.1, clause 4.2.3) for the
columns the benchmark's queries read, in numpy from a seed. Not dbgen's own
random streams: the same distributions and dependencies between columns,
drawn from `numpy.random.default_rng`.

Dates are int32 days since 1970-01-01 (Arrow date32)."""

import numpy as np

STARTDATE = 8035       # 1992-01-01
ENDDATE = 10591        # 1998-12-31


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents, a function of P_PARTKEY."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def order_dates(rng, scale_factor: float) -> np.ndarray:
    """O_ORDERDATE of the 1,500,000 x SF orders: uniform in
    [STARTDATE, ENDDATE - 151 days]."""
    return rng.integers(STARTDATE, ENDDATE - 151 + 1,
                        int(round(1_500_000 * scale_factor)), dtype=np.int32)


def lines_per_order(rng, n_orders: int, n_lines: int) -> np.ndarray:
    """1 to 7 lines an order, uniform, then single lines added to or taken
    from orders drawn at random until the table has `n_lines` rows: every
    seed makes a table of the same size (the draw's own sum is within a few
    thousand of 4 an order)."""
    per_order = rng.integers(1, 8, n_orders)
    while (short := n_lines - int(per_order.sum())) != 0:
        room = np.flatnonzero(per_order < 7 if short > 0 else per_order > 1)
        pick = rng.choice(room, min(abs(short), len(room)), replace=False)
        per_order[pick] += 1 if short > 0 else -1
    return per_order


def lineitems(rng, n_lines: int, scale_factor: float) -> dict:
    """`n_lines` rows in order of their orders, 1 to 7 lines an order:
    L_QUANTITY in [1..50]; L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE of a
    part drawn from the 200,000 x SF; L_DISCOUNT in [0.00..0.10];
    L_SHIPDATE = O_ORDERDATE + [1..121]."""
    odate = order_dates(rng, scale_factor)
    odate = np.repeat(odate, lines_per_order(rng, len(odate), n_lines))
    qty = rng.integers(1, 51, n_lines)
    partkey = rng.integers(1, int(round(200_000 * scale_factor)) + 1, n_lines)
    return {
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": (qty * retail_price_cents(partkey)) / 100.0,
        "l_discount": rng.integers(0, 11, n_lines) / 100.0,
        "l_shipdate": (odate + rng.integers(1, 122, n_lines)).astype(np.int32),
    }
