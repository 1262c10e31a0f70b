"""Join exec tests against a python oracle covering all join types, null
keys, duplicates, hash-collision safety and residual conditions."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.exec.basic import InMemoryScanExec
from spark_rapids_tpu.exec.joins import (
    HashJoinExec, NestedLoopJoinExec,
)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.ops.join import (
    BuildTable, expand_candidates, probe_counts, verify_pairs,
)
from spark_rapids_tpu.types import (
    DOUBLE, INT, LONG, STRING, Schema, StructField,
)

L_SCHEMA = Schema((StructField("lk", INT), StructField("lv", STRING)))
R_SCHEMA = Schema((StructField("rk", INT), StructField("rv", STRING)))

L_DATA = {"lk": [1, 2, 2, None, 5, 7], "lv": ["a", "b", "c", "d", "e", "f"]}
R_DATA = {"rk": [2, 2, 3, None, 5, 5], "rv": ["x", "y", "z", "n", "p", "q"]}


def scan(data, schema, split=0):
    n = len(next(iter(data.values())))
    if split:
        batches = [ColumnarBatch.from_pydict(
            {k: v[s:s + split] for k, v in data.items()}, schema)
            for s in range(0, n, split)]
    else:
        batches = [ColumnarBatch.from_pydict(data, schema)]
    return InMemoryScanExec(batches, schema)


def oracle_join(join_type):
    lrows = list(zip(L_DATA["lk"], L_DATA["lv"]))
    rrows = list(zip(R_DATA["rk"], R_DATA["rv"]))
    out = []
    matched_r = set()
    for lk, lv in lrows:
        matches = [(rk, rv) for rk, rv in rrows
                   if lk is not None and rk == lk]
        for i, (rk, rv) in enumerate(rrows):
            if lk is not None and rk == lk:
                matched_r.add(i)
        if matches:
            if join_type in ("inner", "left_outer", "full_outer"):
                out.extend([(lk, lv, rk, rv) for rk, rv in matches])
            elif join_type == "left_semi":
                out.append((lk, lv))
        else:
            if join_type in ("left_outer", "full_outer"):
                out.append((lk, lv, None, None))
            elif join_type == "left_anti":
                out.append((lk, lv))
    if join_type in ("right_outer", "full_outer"):
        for i, (rk, rv) in enumerate(rrows):
            if i not in matched_r:
                out.append((None, None, rk, rv))
    if join_type == "right_outer":
        inner = oracle_join("inner")
        out = inner + out
    return out


@pytest.mark.parametrize("split", [0, 2])
@pytest.mark.parametrize("jt", ["inner", "left_outer", "right_outer",
                                "full_outer", "left_semi", "left_anti"])
def test_hash_join_types(jt, split):
    plan = HashJoinExec(scan(L_DATA, L_SCHEMA, split),
                        scan(R_DATA, R_SCHEMA),
                        [col("lk")], [col("rk")], join_type=jt)
    got = sorted(plan.collect(), key=repr)
    want = sorted(oracle_join(jt), key=repr)
    assert got == want, f"{jt}: {got} != {want}"


def test_hash_join_build_left():
    plan = HashJoinExec(scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
                        [col("lk")], [col("rk")], join_type="inner",
                        build_side="left")
    got = sorted(plan.collect(), key=repr)
    assert got == sorted(oracle_join("inner"), key=repr)


def test_left_outer_build_left():
    plan = HashJoinExec(scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
                        [col("lk")], [col("rk")], join_type="left_outer",
                        build_side="left")
    got = sorted(plan.collect(), key=repr)
    assert got == sorted(oracle_join("left_outer"), key=repr)


def test_join_with_condition():
    # inner join with residual: rv > lv is replaced by int condition
    ldata = {"lk": [1, 1, 2], "lv": ["a", "b", "c"]}
    rdata = {"rk": [1, 1, 2], "rv": ["p", "q", "r"]}
    plan = HashJoinExec(
        scan(ldata, L_SCHEMA), scan(rdata, R_SCHEMA),
        [col("lk")], [col("rk")], join_type="inner",
        condition=(col("lv") == lit("a")))
    got = sorted(plan.collect(), key=repr)
    assert got == [(1, "a", 1, "p"), (1, "a", 1, "q")]


def test_left_outer_condition_unmatched():
    ldata = {"lk": [1, 2], "lv": ["a", "b"]}
    rdata = {"rk": [1, 2], "rv": ["p", "q"]}
    plan = HashJoinExec(
        scan(ldata, L_SCHEMA), scan(rdata, R_SCHEMA),
        [col("lk")], [col("rk")], join_type="left_outer",
        condition=(col("lv") == lit("a")))
    got = sorted(plan.collect(), key=repr)
    assert got == [(1, "a", 1, "p"), (2, "b", None, None)]


def test_string_keys_join():
    lschema = Schema((StructField("lk", STRING), StructField("lv", INT)))
    rschema = Schema((StructField("rk", STRING), StructField("rv", INT)))
    ldata = {"lk": ["aa", "bb", None, "cc"], "lv": [1, 2, 3, 4]}
    rdata = {"rk": ["bb", "cc", "cc", None], "rv": [10, 20, 30, 40]}
    plan = HashJoinExec(scan(ldata, lschema), scan(rdata, rschema),
                        [col("lk")], [col("rk")], join_type="inner")
    got = sorted(plan.collect())
    assert got == [("bb", 2, "bb", 10), ("cc", 4, "cc", 20),
                   ("cc", 4, "cc", 30)]


def test_multi_key_join():
    lschema = Schema((StructField("k1", INT), StructField("k2", STRING),
                      StructField("lv", INT)))
    rschema = Schema((StructField("j1", INT), StructField("j2", STRING),
                      StructField("rv", INT)))
    ldata = {"k1": [1, 1, 2], "k2": ["a", "b", "a"], "lv": [1, 2, 3]}
    rdata = {"j1": [1, 1, 2], "j2": ["a", "a", "b"], "rv": [10, 20, 30]}
    plan = HashJoinExec(scan(ldata, lschema), scan(rdata, rschema),
                        [col("k1"), col("k2")], [col("j1"), col("j2")],
                        join_type="inner")
    got = sorted(plan.collect())
    assert got == [(1, "a", 1, 1, "a", 10), (1, "a", 1, 1, "a", 20)]


def test_existence_join():
    plan = HashJoinExec(scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
                        [col("lk")], [col("rk")], join_type="existence")
    got = {r[0:2]: r[2] for r in plan.collect()}
    assert got[(2, "b")] is True
    assert got[(1, "a")] is False
    assert got[(None, "d")] is False
    assert got[(5, "e")] is True


def test_empty_build_side():
    empty = InMemoryScanExec([], R_SCHEMA)
    plan = HashJoinExec(scan(L_DATA, L_SCHEMA), empty,
                        [col("lk")], [col("rk")], join_type="left_outer")
    got = sorted(plan.collect(), key=repr)
    assert len(got) == 6
    assert all(r[2] is None and r[3] is None for r in got)


def test_cross_join():
    plan = NestedLoopJoinExec(scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
                              join_type="cross", chunk_rows=8)
    assert len(plan.collect()) == 36


def test_nested_loop_inner_condition():
    plan = NestedLoopJoinExec(
        scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
        join_type="inner",
        condition=(col("lk") > col("rk")), chunk_rows=8)
    got = plan.collect()
    want = [(lk, lv, rk, rv)
            for lk, lv in zip(L_DATA["lk"], L_DATA["lv"])
            for rk, rv in zip(R_DATA["rk"], R_DATA["rv"])
            if lk is not None and rk is not None and lk > rk]
    assert sorted(got) == sorted(want)


def test_nested_loop_left_outer():
    plan = NestedLoopJoinExec(
        scan(L_DATA, L_SCHEMA), scan(R_DATA, R_SCHEMA),
        join_type="left_outer",
        condition=(col("lk") > col("rk")), chunk_rows=4)
    got = plan.collect()
    matched = {lk for lk, _ in zip(L_DATA["lk"], L_DATA["lv"])
               if lk is not None and any(rk is not None and lk > rk
                                         for rk in R_DATA["rk"])}
    unmatched_rows = [r for r in got if r[2] is None and r[3] is None]
    assert {r[0] for r in unmatched_rows} == {1, 2, None}


# --- the probe itself (ops/join.py): counts + expand + verify ---------------


def _key_col(values, dtype, null_every=0):
    c = Column.from_numpy(values, dtype,
                          capacity=bucket_capacity(len(values)))
    if null_every:
        v = np.asarray(c.validity).copy()
        v[::null_every] = False
        c = Column(c.data, jnp.asarray(v), dtype)
    return c


def _long_keys(seed, nb, ns, dom, null_every):
    rng = np.random.default_rng(seed)
    # negative LONGs: every high bit of the 64-bit key set
    bk = _key_col(rng.integers(-dom, dom, nb).astype(np.int64), LONG,
                  null_every)
    sk = _key_col(rng.integers(-dom, dom, ns).astype(np.int64), LONG,
                  max(0, null_every - 2))
    return [bk], [sk], nb, ns, None


def _two_column_keys():
    """(LONG, INT) keys with nulls in one column of each side."""
    rng = np.random.default_rng(4)
    nb, ns = 400, 900
    bks = [_key_col(rng.integers(0, 50, nb).astype(np.int64), LONG, 5),
           _key_col(rng.integers(0, 7, nb).astype(np.int32), INT)]
    sks = [_key_col(rng.integers(0, 50, ns).astype(np.int64), LONG),
           _key_col(rng.integers(0, 7, ns).astype(np.int32), INT, 9)]
    return bks, sks, nb, ns, None


def _disjoint_keys(cand_cap):
    bk = _key_col(np.arange(100, dtype=np.int64), LONG)
    sk = _key_col((np.arange(300) + 1000).astype(np.int64), LONG)
    return [bk], [sk], 100, 300, cand_cap


def _one_key_overflowing():
    """64 x 64 equal keys, 4096 candidates, a bucket of 1024 (the
    speculative cached-bucket overflow shape): the expansion keeps the
    first 1024 in stream-row order, 16 stream rows' whole ranges."""
    k = _key_col(np.zeros(64, np.int64), LONG)
    return [k], [k], 64, 64, 1024


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _long_keys(0, 500, 1500, 200, 7),
                 id="long-duplicates-nulls"),
    pytest.param(lambda: _long_keys(1, 64, 200, 1000, 0),
                 id="long-sparse-no-nulls"),
    pytest.param(lambda: _long_keys(2, 300, 300, 5, 3),
                 id="long-heavy-duplication"),
    pytest.param(lambda: _long_keys(3, 1, 100, 2, 0),
                 id="long-single-row-build"),
    pytest.param(_two_column_keys, id="two-column-int-keys"),
    pytest.param(lambda: _disjoint_keys(128), id="no-match-cap-128"),
    pytest.param(lambda: _disjoint_keys(256), id="no-match-cap-256"),
    pytest.param(_one_key_overflowing, id="cap-under-candidate-count"),
])
def test_probe_verified_pairs_match_oracle(case):
    """The verified (stream row, build row) pairs of probe_counts +
    expand_candidates + verify_pairs are exactly the pairs of rows
    whose keys are equal and non-null on both sides; with a candidate
    bucket under the true count, exactly those among the first
    `cand_cap` candidates in stream-row order."""
    bks, sks, nb, ns, cand_cap = case()
    build = BuildTable.build(bks, bks, jnp.int32(nb), bks[0].capacity)
    lo, counts, _ = probe_counts(build, sks, jnp.int32(ns),
                                 sks[0].capacity)
    total = int(jnp.sum(counts))
    if cand_cap is None:
        cand_cap = bucket_capacity(max(total, 1))
    s_idx, b_pos, total_dev = expand_candidates(lo, counts, cand_cap)
    assert int(total_dev) == total
    pair_valid = s_idx >= 0
    ok, b_row = verify_pairs(build, sks,
                             jnp.where(pair_valid, s_idx, -1),
                             jnp.where(pair_valid, b_pos, -1), pair_valid)
    ok = np.asarray(ok)
    got = sorted(zip(np.asarray(s_idx)[ok].tolist(),
                     np.asarray(b_row)[ok].tolist()))

    def rows(cols, n):
        data = [np.asarray(c.data)[:n] for c in cols]
        valid = np.logical_and.reduce(
            [np.asarray(c.validity)[:n] for c in cols])
        return [tuple(int(d[i]) for d in data) if valid[i] else None
                for i in range(n)]
    bkeys, skeys = rows(bks, nb), rows(sks, ns)
    want = [(s, b) for s in range(ns) for b in range(nb)
            if skeys[s] is not None and skeys[s] == bkeys[b]]
    if total > cand_cap:
        # every candidate here is a true match (one key): the kept
        # ones are the first cand_cap in (stream row, range) order
        assert len(want) == total
        kept = {s for s, _ in want[:cand_cap]}
        assert len(got) == cand_cap and {s for s, _ in got} == kept
        want = [p for p in want if p[0] in kept]
    assert got == want


# --- session level: planner-built join against a python oracle --------------


def _session_join_tables():
    rng = np.random.default_rng(11)
    no, nl = 180, 500
    orders = {"o_key": rng.integers(0, 150, no).tolist(),
              "o_flag": rng.integers(0, 10, no).tolist(),
              "o_name": [f"o{i % 17}" for i in range(no)]}
    lines = {"l_key": [int(k) if i % 6 else None
                       for i, k in enumerate(rng.integers(0, 150, nl))],
             "l_val": (rng.random(nl) * 100).round(6).tolist()}
    return orders, lines


@pytest.mark.parametrize("how", ["inner", "left_outer", "left_semi",
                                 "left_anti"])
def test_session_join_matches_oracle(how):
    """A join planned by the session (LONG keys with nulls on the stream
    side, duplicate build keys, a string payload) returns the python
    oracle's row multiset."""
    from spark_rapids_tpu.api.session import TpuSession
    orders, lines = _session_join_tables()
    sess = TpuSession()
    df_o = sess.from_pydict(orders, Schema((
        StructField("o_key", LONG), StructField("o_flag", INT),
        StructField("o_name", STRING))))
    df_l = sess.from_pydict(lines, Schema((
        StructField("l_key", LONG, True), StructField("l_val", DOUBLE))))
    got = df_l.join(df_o, left_on="l_key", right_on="o_key",
                    how=how).collect()
    orows = list(zip(orders["o_key"], orders["o_flag"], orders["o_name"]))
    want = []
    for lrow in zip(lines["l_key"], lines["l_val"]):
        matches = [o for o in orows
                   if lrow[0] is not None and o[0] == lrow[0]]
        if how in ("inner", "left_outer"):
            want.extend(lrow + o for o in matches)
            if how == "left_outer" and not matches:
                want.append(lrow + (None, None, None))
        elif bool(matches) == (how == "left_semi"):
            want.append(lrow)

    def order(rows):
        return sorted(map(tuple, rows),
                      key=lambda r: tuple((x is None, x) for x in r))
    assert order(got) == order(want) and got
