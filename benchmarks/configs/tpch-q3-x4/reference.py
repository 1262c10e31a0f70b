"""tpch-q3-x4: the same data, plain reference and comparison as `tpch-q3`
(loaded from its file), and the work model with the exchange's entry.
Imports nothing of the program under test; numpy only."""

import importlib.util
import os

import numpy as np

_Q3 = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tpch-q3", "reference.py")
_spec = importlib.util.spec_from_file_location("bench_tpch_q3_x4_q3ref", _Q3)
q3 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(q3)

generate, reference, as_rows, compare = (q3.generate, q3.reference,
                                         q3.as_rows, q3.compare)
tied_runs, smallest_gap = q3.tied_runs, q3.smallest_gap
WIDTHS = q3.WIDTHS


def least_ici_bytes(cfg: dict, tables: dict) -> int:
    """The least any plan of Q3 on `partitions` chips must move between
    chips when each chip starts from an equal share of every table's rows:
    the smaller input of each join sent to the other chips at its columns'
    widths (the customers of SEGMENT, their keys; the orders placed before
    DATE by those customers, key, date and priority), and the partial
    groups of every chip but the one that finishes them (key, date,
    priority and a revenue). Whatever the plan, the exchange's share of its
    roofline then stays under 100%."""
    n = int(cfg["partitions"])
    cust, orders = tables["customer"], tables["orders"]
    seg = np.asarray(cust["c_mktsegment"], dtype=object) \
        == cfg["params"]["segment"]
    seg_keys = np.asarray(cust["c_custkey"])[seg]
    early = orders["o_orderdate"] < q3.cut_date(cfg)
    placed = int(np.isin(orders["o_custkey"][early], seg_keys).sum())
    groups = len(np.unique(q3.joined(tables, cfg)["l_orderkey"]))
    order_w = WIDTHS["BIGINT"] + WIDTHS["DATE"] + WIDTHS["INT"]
    group_w = order_w + WIDTHS["DOUBLE"]
    return int(len(seg_keys) * WIDTHS["BIGINT"] * (n - 1)
               + placed * order_w * (n - 1)
               + groups * group_w * (n - 1) / n)


def work_model(cfg: dict, tables: dict) -> dict:
    """`tpch-q3`'s one entry, `join_groupby` (the bytes the query needs
    touched in HBM, memory-bound), with the bytes it must move between
    chips beside them under `ici_bytes`: not `bytes`, so that
    `query_hbm_share` does not count them as HBM traffic, and in the same
    entry, since the harness holds every entry of a work model to a
    memory-bound `bytes`."""
    work = q3.work_model(cfg, tables)
    work["join_groupby"]["ici_bytes"] = least_ici_bytes(cfg, tables)
    return work
