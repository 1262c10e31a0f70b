"""Host syncs through one door (ISSUE 40): `obs.dispatch.host_read(site,
value)` is the only way a device-to-host read happens on the served path.

(a) the chokepoint: counts and ns by site, phase `host-sync` only outside
    every other span, the closed phase books, the ledger-off path;
(b) the four cells' served path at the rehearsal size: with the
    conversions of a device array wrapped, none happens outside the door
    during `collect()`;
(c) per-site counts are identical over two warm `collect()`s of one plan;
(d) the lazy harvest: `collect()` reads no metric, and the accessors give
    what the eager harvest gave, on a q3-shaped plan and on a query that
    raised mid-plan."""

import collections
import sys
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jarray

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import lifecycle
from spark_rapids_tpu.exec import task_metrics
from spark_rapids_tpu.obs import dispatch, op_span
from spark_rapids_tpu.obs import phase as obs_phase
from spark_rapids_tpu.obs.profile import QueryProfile
from spark_rapids_tpu.types import LONG, Schema

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import datagen  # noqa: E402
from benchmarks.lib.manifest import Manifest, apply_rehearsal  # noqa: E402

CELLS = ("q6_scan_filter_sum", "q14_join_like_ratio",
         "q1_groupby_string_keys", "q3_join_groupby_topn")
SEED = 3000000019


def _reads(before, after):
    return {site: rec["reads"] - before.get(site, {}).get("reads", 0)
            for site, rec in after.items()
            if rec["reads"] - before.get(site, {}).get("reads", 0)}


# -- (a) the chokepoint --------------------------------------------------------

def test_reads_are_counted_and_timed_by_site():
    dispatch.reset_dispatch_ledger()
    x = jnp.arange(8, dtype=jnp.int32)
    assert int(dispatch.host_read("fx.total", x.sum())) == 28
    got = dispatch.host_read("fx.total", (x, x[:2]))
    assert isinstance(got[0], np.ndarray) and got[1].tolist() == [0, 1]
    assert dispatch.host_read("fx.wait", x, fetch=False) is x
    sites = dispatch.sync_sites()
    assert {s: r["reads"] for s, r in sites.items()} == \
        {"fx.total": 2, "fx.wait": 1}
    assert all(r["ns"] > 0 for r in sites.values())
    c = dispatch.counters()
    assert c["syncs"] == 3
    assert c["sync_ns"] == sum(r["ns"] for r in sites.values())
    top = dispatch.health_section()["top_sync_sites"]
    assert {t["site"] for t in top} == {"fx.total", "fx.wait"}
    assert top[0]["ns"] >= top[1]["ns"]


def test_a_value_with_nothing_on_the_device_is_no_read():
    dispatch.reset_dispatch_ledger()
    host = np.arange(3)
    assert dispatch.host_read("fx.host", 7) == 7
    assert dispatch.host_read("fx.host", host).tolist() == [0, 1, 2]
    assert dispatch.sync_sites() == {} and dispatch.counters()["syncs"] == 0


def test_host_sync_accrues_outside_every_span_only():
    dispatch.reset_dispatch_ledger()
    x = jnp.arange(1 << 12, dtype=jnp.float32)
    g0 = obs_phase.counters()
    with lifecycle.governed() as ctx:
        led = obs_phase.attach(ctx)
        dispatch.host_read("fx.outside", x * 2)
        with op_span("agg.update", phase="group-agg"):
            dispatch.host_read("fx.inside", x * 3)
    g1 = obs_phase.counters()
    sites = dispatch.sync_sites()
    ph = led.snapshot()
    assert sum(ph.values()) == led.wall_ns
    assert 0 < ph["host-sync"] <= sites["fx.outside"]["ns"]
    # the read inside the span is the span's phase, not host-sync
    assert ph["group-agg"] >= sites["fx.inside"]["ns"]
    assert g1["host-sync"] - g0["host-sync"] == ph["host-sync"]


def test_with_the_ledger_off_there_is_no_count_and_the_value_is_the_same(
        monkeypatch):
    x = jnp.linspace(0.0, 1.0, 17, dtype=jnp.float32)
    on = dispatch.host_read("fx.value", x)
    monkeypatch.setattr(dispatch, "_ledger", None)
    spans = []
    monkeypatch.setattr("spark_rapids_tpu.obs.span.op_span",
                        lambda *a, **k: spans.append(a))
    off = dispatch.host_read("fx.value", x)
    assert dispatch.counters()["syncs"] == 0 and dispatch.sync_sites() == {}
    assert spans == []
    assert off.dtype == on.dtype and off.tobytes() == on.tobytes()
    assert dispatch.host_read("fx.wait", x, fetch=False) is x


# -- (b), (c): the four cells' served path -------------------------------------

class _Watch:
    """Every conversion of a device array to host memory while armed: the
    array's `_value` (int(), bool(), .tolist(), device_get, np.asarray on a
    chip) and `np.asarray` / `np.array` of one (the CPU backend hands numpy
    the buffer without `_value`), by the engine frame that asked, when it
    is not inside `host_read`."""

    def __init__(self, monkeypatch):
        self.armed = False
        self.outside = collections.Counter()
        value = jarray.ArrayImpl._value
        watch = self

        def _value(arr):
            watch.note()
            return value.fget(arr)

        monkeypatch.setattr(jarray.ArrayImpl, "_value", property(_value))
        for name in ("asarray", "array"):
            orig = getattr(np, name)

            def conv(a, *args, _orig=orig, **kw):
                if isinstance(a, jarray.ArrayImpl):
                    watch.note()
                return _orig(a, *args, **kw)
            monkeypatch.setattr(np, name, conv)

    def note(self):
        if not self.armed or dispatch.in_host_read():
            return
        frames = [f for f in traceback.extract_stack()[:-2]
                  if "spark_rapids_tpu" in f.filename]
        if frames:
            f = frames[-1]
            self.outside[f"{Path(f.filename).name}:{f.lineno} {f.name}"] += 1


@pytest.fixture(scope="module")
def cell_data(tmp_path_factory):
    m = Manifest()
    out = {}
    for cell in CELLS:
        cfg = apply_rehearsal(m.config(m.cell(cell)["config"]))
        tables = m.config_module(cfg, "reference").generate(SEED, cfg)
        paths = datagen.write_tables(str(tmp_path_factory.mktemp(cell)),
                                     tables, cfg["schema"], cfg["layout"])
        out[cell] = (cfg, m.config_module(cfg, "query"), paths)
    return out


@pytest.fixture(scope="module")
def served(cell_data):
    """Each cell's query driven three times through one DataFrame: the
    cold collect, then two warm ones, each under the watch."""
    mp = pytest.MonkeyPatch()
    watch = _Watch(mp)
    out = {}
    try:
        for cell, (cfg, query, paths) in cell_data.items():
            sess = TpuSession(dict(cfg.get("session_conf", {})))
            df = query.build(sess, paths, cfg)
            watch.outside.clear()
            per_collect = []
            for _ in range(3):
                s0 = dispatch.sync_sites()
                watch.armed = True
                try:
                    df.collect()
                finally:
                    watch.armed = False
                per_collect.append(_reads(s0, dispatch.sync_sites()))
            out[cell] = (dict(watch.outside), per_collect)
    finally:
        mp.undo()
        TpuSession()
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_every_read_of_a_collect_goes_through_the_door(served, cell):
    outside, per_collect = served[cell]
    assert outside == {}, outside
    # and the door saw the reads: at least the result's
    assert all(reads.get("result.fetch", 0) >= 1 for reads in per_collect)
    # no metric is read inside a collect (the harvest is lazy)
    assert all("metric.rows" not in reads for reads in per_collect)


@pytest.mark.parametrize("cell", CELLS)
def test_per_site_counts_are_the_same_in_every_warm_collect(served, cell):
    _, (_cold, warm1, warm2) = served[cell]
    assert warm1 == warm2 and warm1


# -- (d) the lazy harvest ------------------------------------------------------

def _eager_too(monkeypatch):
    """Have each QueryHarvest also take the EAGER harvest at the query's
    end, as `_collect_once` did before PR 40, without consuming what the
    lazy one reads later: every metric's state is put back after."""
    eager = []
    init = task_metrics.QueryHarvest.__init__

    def both(self, plan, before, **kw):
        init(self, plan, before, **kw)
        states = []

        def save(node):
            for m in node.metrics.values():
                states.append((m, m._value, list(m._pending)))
            for c in node.children:
                save(c)
        save(plan)
        summary = task_metrics.query_summary(plan, before, self._after,
                                             self._level)
        profile = QueryProfile(plan, summary, level=self._level,
                               statistics=kw.get("statistics"),
                               phases=kw.get("phases"))
        for m, value, pending in states:
            m._value, m._pending = value, pending
        eager.append((summary, profile))

    monkeypatch.setattr(task_metrics.QueryHarvest, "__init__", both)
    return eager


def test_collect_reads_no_metric_and_the_accessors_give_the_eager_values(
        cell_data, monkeypatch):
    cfg, query, paths = cell_data["q3_join_groupby_topn"]
    conf = dict(cfg.get("session_conf", {}))
    a = TpuSession(conf)
    query.build(a, paths, cfg).collect()          # cold: compiles, trips
    s0 = dispatch.sync_sites()
    rows = query.build(a, paths, cfg).collect()
    assert rows and "metric.rows" not in _reads(s0, dispatch.sync_sites())
    eager = _eager_too(monkeypatch)
    assert query.build(a, paths, cfg).collect() == rows
    # another session runs the same plan shape before anyone reads A's: the
    # program-site cache hands A's programs to B's execs
    b = TpuSession(conf)
    query.build(b, paths, cfg).collect()
    summary, profile = eager[0]
    s1 = dispatch.sync_sites()
    assert a.last_query_metrics() == summary
    assert _reads(s1, dispatch.sync_sites()).get("metric.rows", 0) >= 1
    assert a.last_query_profile().to_json() == profile.to_json()
    assert a.last_query_metrics() is a.last_query_metrics()   # cached
    assert summary["total.numOutputRows"] > 0
    TpuSession()


def test_a_query_that_raised_mid_plan_keeps_its_metrics(monkeypatch):
    sess = TpuSession({"spark.rapids.tpu.task.maxAttempts": "1",
                       "spark.rapids.sql.batchSizeBytes": "4k"})
    n = 2000
    df = sess.from_pydict({"a": list(range(n))}, Schema.of(a=LONG),
                          batch_rows=250)
    from spark_rapids_tpu.expr.core import col, lit
    seen = {"n": 0}

    def fail_third(it):
        for pdf in it:
            seen["n"] += 1
            if seen["n"] == 3:
                raise ValueError("mid-plan")
            yield pdf

    eager = _eager_too(monkeypatch)
    q = df.filter(col("a") % lit(3) == lit(0)) \
        .map_in_pandas(fail_third, Schema.of(a=LONG))
    with pytest.raises(Exception, match="mid-plan"):
        q.collect()
    summary, profile = eager[-1]
    got = sess.last_query_metrics()
    assert got == summary
    assert sess.last_query_profile().to_json() == profile.to_json()
    # the operators below the failure counted what they passed on
    assert any(k.endswith("numOutputRows") and v > 0 for k, v in got.items())
    TpuSession()
