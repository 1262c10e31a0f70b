"""TPC-H's population rules (specification v3.0.1, clauses 4.2.2.13 and
4.2.3) for `part` and for the lineitem columns that join it, beside
`dbgen.py` (which no later PR edits, and whose `lineitems()` does not
return the part key it draws). The dependencies between columns are the
specification's and `dbgen.py`'s: order dates, lines an order and the
retail price come from there. numpy only."""

import numpy as np

from . import dbgen

#: P_TYPE is three syllables, one of each list (clause 4.2.2.13, "Types")
TYPE_SYLLABLE_1 = ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
TYPE_SYLLABLE_2 = ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
TYPE_SYLLABLE_3 = ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
P_TYPES = tuple(f"{a} {b} {c}" for a in TYPE_SYLLABLE_1
                for b in TYPE_SYLLABLE_2 for c in TYPE_SYLLABLE_3)


def part_count(scale_factor: float) -> int:
    return int(round(200_000 * scale_factor))


def parts(rng, n: int) -> dict:
    """`n` parts: P_PARTKEY dense in 1..n in key order; P_TYPE one of the
    150 three-syllable strings, drawn uniformly. `p_type` is a list of
    `str`, which `pyarrow.array` takes as a string column."""
    types = np.array(P_TYPES, dtype=object)[rng.integers(0, len(P_TYPES), n)]
    return {"p_partkey": np.arange(1, n + 1, dtype=np.int64),
            "p_type": types.tolist()}


def lineitems_with_partkey(rng, n_lines: int, scale_factor: float) -> dict:
    """`dbgen.lineitems` with the part key it draws kept: `n_lines` rows in
    order of their orders, 1 to 7 lines an order; L_PARTKEY uniform over the
    200,000 x SF parts; L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE of that
    part; L_DISCOUNT in [0.00..0.10]; L_SHIPDATE = O_ORDERDATE + [1..121]."""
    odate = dbgen.order_dates(rng, scale_factor)
    odate = np.repeat(odate, dbgen.lines_per_order(rng, len(odate), n_lines))
    qty = rng.integers(1, 51, n_lines)
    partkey = rng.integers(1, part_count(scale_factor) + 1, n_lines)
    return {
        "l_partkey": partkey.astype(np.int64),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": (qty * dbgen.retail_price_cents(partkey)) / 100.0,
        "l_discount": rng.integers(0, 11, n_lines) / 100.0,
        "l_shipdate": (odate + rng.integers(1, 122, n_lines)).astype(np.int32),
    }
