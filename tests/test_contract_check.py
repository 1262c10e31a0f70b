"""Engine contract analyzer (ISSUE 12): per-rule fixture corpus (each
rule fires on its fixture, a justified suppression silences it), the
suppression-justification and baseline lints, the CLI JSON surface, and
THE tier-1 gate — the whole package analyzes clean against the
checked-in baseline."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = ROOT / "tests" / "fixtures" / "contract"

from spark_rapids_tpu import analysis  # noqa: E402
from spark_rapids_tpu.analysis import core as acore  # noqa: E402
from spark_rapids_tpu.analysis import registry as reg_mod  # noqa: E402
from spark_rapids_tpu.analysis.registry import (  # noqa: E402
    ContractRegistry, EntrySpec, LockSpec, PairSpec)

sys.path.insert(0, str(ROOT / "tools"))
try:
    import contract_check
finally:
    sys.path.pop(0)


def fixture_registry(fname: str) -> ContractRegistry:
    """Fixture twin of DEFAULT_REGISTRY scoped to one fixture module
    (module matching is suffix-based, so each fixture file gets its own
    specs)."""
    return ContractRegistry(
        locks=[
            LockSpec("fx-outer", fname, "Engine", "self._outer",
                     reentrant=False, note="fixture outer lock"),
            LockSpec("fx-lock", fname, "Engine", "self._lock",
                     reentrant=False, note="fixture lock"),
        ],
        lock_order=["fx-outer", "fx-lock"],
        cross_query_entries=[
            EntrySpec(fname, None, "writer_loop", "fixture producer")],
        pairs=[PairSpec("fx-budget", "reserve", "release", "budget",
                        (fname,),
                        {"escrowed": "fixture: ownership transfers"})],
        adopt_helpers=reg_mod.ADOPT_HELPERS,
        extra_blocking_calls={},
        scope_prefix="",  # fixtures live under tests/, not the package
    )


def run_fixture(fname: str, rules=None):
    return analysis.analyze_paths([FIXTURES / fname], ROOT,
                                  registry=fixture_registry(fname),
                                  rules=rules)


def rules_fired(report):
    return sorted({f.rule for f in report.findings})


# -- per-rule: fixture fires ------------------------------------------------

def test_lock_rules_fire():
    rep = run_fixture("fx_locks.py")
    by_rule = {}
    for f in rep.findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"lock-blocking-call", "lock-reacquire",
                            "lock-order"}
    # the direct sleep AND the one reached through the module-local walk
    blocking_scopes = {f.scope for f in by_rule["lock-blocking-call"]}
    assert "Engine.bad_blocking" in blocking_scopes
    assert "Engine._do_io" in blocking_scopes  # via bad_blocking_via_call
    assert by_rule["lock-reacquire"][0].key == "fx-lock"
    assert by_rule["lock-order"][0].key == "fx-lock->fx-outer"


def test_bounded_wait_rule_fires():
    """ISSUE 20 satellite: provably unbounded waits (zero positional
    args, no timeout= kwarg) on wait/get/result/sleep fire; bounded,
    positional-arg (dict.get) and splat forms stay clean."""
    rep = run_fixture("fx_bounded_wait.py")
    assert rules_fired(rep) == ["bounded-wait"]
    got = {(f.scope, f.key) for f in rep.findings}
    assert got == {("parked_on_event", "ev.wait"),
                   ("parked_on_queue", "q.get"),
                   ("parked_on_future", "fut.result")}, got


def test_thread_rule_fires_and_resolves_adoption():
    rep = run_fixture("fx_threads.py")
    assert rules_fired(rep) == ["thread-adopt"]
    scopes = {f.scope for f in rep.findings}
    assert scopes == {"spawn_bad", "submit_bad"}  # spawn_good is clean


def test_trace_rules_fire():
    rep = run_fixture("fx_trace.py")
    by_rule = {}
    for f in rep.findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"trace-module-jnp", "trace-host-sync"}
    assert [f.key for f in by_rule["trace-module-jnp"]] == ["_BAD"]
    assert {f.scope for f in by_rule["trace-host-sync"]} == \
        {"traced", "add_kernel"}  # `untraced` stays clean


def test_conf_rule_fires_only_from_entry():
    rep = run_fixture("fx_conf.py")
    assert rules_fired(rep) == ["conf-provenance"]
    assert len(rep.findings) == 1
    assert rep.findings[0].scope == "_helper"  # via writer_loop;
    # consumer_side's read is NOT reachable from the entry


def test_accounting_rule_shapes():
    rep = run_fixture("fx_accounting.py")
    assert rules_fired(rep) == ["accounting-symmetry"]
    keys = {f.scope: f.key for f in rep.findings}
    assert keys == {"one_sided": "fx-budget:one-sided",
                    "exception_edge": "fx-budget:exception-edge"}
    # guarded (finally) and escrowed (registry-declared) stay clean


def test_dispatch_rule_fires():
    """ISSUE 13 satellite: every jax.jit / pallas_call site must route
    through the dispatch-ledger chokepoint (obs.dispatch.instrument)
    or carry a justified suppression — a bare site's dispatches and
    compiles are invisible to the observability plane."""
    rep = run_fixture("fx_dispatch.py")
    assert rules_fired(rep) == ["dispatch-ledger"]
    keys = sorted(f.key for f in rep.findings)
    assert keys == ["jax.jit", "jax.jit", "pallas_call"], keys


def test_host_sync_rule_fires():
    """ISSUE 40 satellite: every jax.device_get / .block_until_ready()
    must go through the host-read chokepoint (obs.dispatch.host_read) or
    carry a justified suppression — a bare read is a sync the ledger
    cannot count by site and the trace cannot name."""
    rep = run_fixture("fx_host_sync.py")
    assert rules_fired(rep) == ["host-sync-chokepoint"]
    keys = sorted(f.key for f in rep.findings)
    assert keys == ["block_until_ready", "block_until_ready",
                    "jax.device_get"], keys


def test_stage_governance_rule_fires():
    """ISSUE 14 satellite: per-batch governance hooks (lifecycle tick,
    chaos fault points, metric timers, event emits, gather observes)
    are forbidden inside traced stage bodies handed to the dispatch
    chokepoint — they run once per TRACE, not per batch. The rule
    resolves local defs, self._method references, lambdas, partial
    wrappers and @partial(instrument, ...) decorators, and walks one
    hop into module-local helpers."""
    rep = run_fixture("fx_stage.py")
    assert rules_fired(rep) == ["stage-governance"]
    keys = sorted(f.key for f in rep.findings)
    assert keys == ["emit", "faults.check", "ns_timer", "observe",
                    "tick"], keys
    scopes = {f.key: f.scope for f in rep.findings}
    assert scopes["ns_timer"] == "_kernel"      # self._site(self._m)
    assert scopes["emit"] == "decorated_body"   # @partial(instrument)
    assert scopes["observe"] == "<lambda>"      # one-hop via helper


def test_registry_rules_fire():
    rep = run_fixture("fx_registry.py")
    assert rules_fired(rep) == ["conf-key-registered",
                                "event-kind-registered"]
    assert {f.key for f in rep.findings} == \
        {"spark.rapids.tpu.fixture.not.registered",
         "fixture_unregistered_kind"}


# -- per-rule: suppression silences -----------------------------------------

@pytest.mark.parametrize("fname,n_suppressed", [
    ("fx_locks_ok.py", 4),
    ("fx_bounded_wait_ok.py", 3),
    ("fx_threads_ok.py", 2),
    ("fx_trace_ok.py", 4),
    ("fx_conf_ok.py", 1),
    ("fx_accounting_ok.py", 2),
    ("fx_registry_ok.py", 2),
    ("fx_dispatch_ok.py", 2),
    ("fx_host_sync_ok.py", 1),
    ("fx_stage_ok.py", 1),
])
def test_suppressions_silence(fname, n_suppressed):
    rep = run_fixture(fname)
    assert rep.findings == [], [f.render() for f in rep.findings]
    assert len(rep.suppressed) == n_suppressed
    for _f, why, _line in rep.suppressed:
        assert why.strip(), "suppression accepted without justification"


def test_empty_justification_is_its_own_finding():
    rep = run_fixture("fx_suppress_empty.py")
    meta = [f for f in rep.findings if f.rule == "suppression-empty"]
    # one empty why + one typo'd rule id
    assert len(meta) == 2
    assert {f.key for f in meta} == {"lock-blocking-call",
                                     "lock-blocking-cal"}
    # the empty-why suppression still silenced its base finding (CI
    # fails on the meta finding, not on double noise) while the typo'd
    # one silenced NOTHING
    real = [f for f in rep.findings if f.rule == "lock-blocking-call"]
    assert len(real) == 1 and real[0].scope == "Engine.typo"


# -- baseline ---------------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    rep = run_fixture("fx_locks.py")
    findings = rep.sorted_findings()
    bl_path = tmp_path / "baseline.json"
    entries = acore.write_baseline(bl_path, findings)
    # write mode stamps new entries UNREVIEWED...
    assert all(e["why"] == acore.UNREVIEWED_WHY for e in entries.values())
    loaded = acore.load_baseline(bl_path)
    new, stale, lint = acore.apply_baseline(findings, loaded)
    assert new == [] and stale == []
    # ...and the lint rejects the UNREVIEWED stamp until justified
    assert lint and all(f.rule == "baseline-invalid" for f in lint)
    for e in loaded.values():
        e["why"] = "fixture: accepted"
    new, stale, lint = acore.apply_baseline(findings, loaded)
    assert (new, stale, lint) == ([], [], [])
    # a fixed finding leaves its entry STALE (the file must shrink)
    new, stale, lint = acore.apply_baseline(findings[1:], loaded)
    assert len(stale) == 1 and new == []
    # count semantics: two identical findings, one baselined slot
    dup = [findings[0], findings[0]]
    one = {findings[0].fingerprint: {"count": 1, "why": "x"}}
    new, _stale, _lint = acore.apply_baseline(dup, one)
    assert len(new) == 1


def test_partially_fixed_baseline_entry_is_stale():
    """A count=2 entry with only one finding left must fail as stale —
    the leftover slot would otherwise silently absorb a future
    regression of the same fingerprint (review round fix)."""
    rep = run_fixture("fx_locks.py")
    f = rep.sorted_findings()[0]
    baseline = {f.fingerprint: {"count": 2, "why": "accepted debt"}}
    new, stale, lint = acore.apply_baseline([f], baseline)
    assert new == [] and lint == []
    assert stale == [f.fingerprint]
    # both slots consumed -> clean
    new, stale, _ = acore.apply_baseline([f, f], baseline)
    assert new == [] and stale == []


def test_baseline_write_refuses_scoped_runs(tmp_path, monkeypatch, capsys):
    """`--baseline write` on a path- or rule-scoped run would rewrite
    the whole file from a slice of the findings, destroying every
    out-of-scope entry and its justification — it must refuse."""
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps(
        {"version": 1,
         "findings": {"keep::me::alive::slot":
                      {"count": 1, "why": "precious"}}}))
    monkeypatch.setattr(contract_check, "DEFAULT_BASELINE", bl)
    assert contract_check.main(
        [str(FIXTURES / "fx_registry.py"), "--baseline", "write"]) == 2
    assert contract_check.main(
        ["--rules", "conf-key-registered", "--baseline", "write"]) == 2
    capsys.readouterr()
    assert "precious" in bl.read_text()  # untouched


def test_baseline_write_preserves_existing_whys(tmp_path, monkeypatch):
    monkeypatch.setattr(contract_check, "DEFAULT_BASELINE",
                        tmp_path / "bl.json")
    rep = run_fixture("fx_registry.py")
    prev = {rep.sorted_findings()[0].fingerprint:
            {"count": 1, "why": "kept justification"}}
    entries = acore.write_baseline(tmp_path / "bl.json",
                                   rep.sorted_findings(), prev)
    kept = entries[rep.sorted_findings()[0].fingerprint]
    assert kept["why"] == "kept justification"


# -- CLI --------------------------------------------------------------------

def test_cli_json_golden(tmp_path, capsys):
    """`--format json` on a firing fixture: nonzero exit + the stable
    record shape downstream tooling parses."""
    rc = contract_check.main([
        str(FIXTURES / "fx_registry.py"), "--format", "json",
        "--baseline", str(tmp_path / "missing.json")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["exit"] == 1
    assert out["files_scanned"] == 1
    assert out["stale_baseline"] == [] and out["baseline_lint"] == []
    got = {(f["rule"], f["key"], f["scope"]) for f in out["findings"]}
    assert got == {
        ("conf-key-registered",
         "spark.rapids.tpu.fixture.not.registered", "<module>"),
        ("event-kind-registered", "fixture_unregistered_kind",
         "<module>"),
    }
    for f in out["findings"]:
        assert set(f) == {"rule", "path", "line", "scope", "key",
                          "message", "fingerprint"}


def test_cli_clean_exit_zero(tmp_path, capsys):
    rc = contract_check.main([
        str(FIXTURES / "fx_registry_ok.py"), "--format", "json",
        "--baseline", str(tmp_path / "missing.json")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["findings"] == [] and out["suppressed"] == 2


def test_cli_rule_filter(tmp_path, capsys):
    rc = contract_check.main([
        str(FIXTURES / "fx_registry.py"), "--format", "json",
        "--rules", "conf-key-registered",
        "--baseline", str(tmp_path / "missing.json")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["rule"] for f in out["findings"]} == {"conf-key-registered"}


# -- registry/docs coherence ------------------------------------------------

def test_registry_specs_name_real_modules():
    """Every lock/entry/pair spec in DEFAULT_REGISTRY must point at an
    existing package module — a refactor that moves a file must move
    its contract data too."""
    reg = reg_mod.DEFAULT_REGISTRY
    pkg = ROOT / "spark_rapids_tpu"
    modules = {p.relative_to(ROOT).as_posix()
               for p in pkg.rglob("*.py")}

    def exists(suffix):
        return any(m.endswith(suffix) for m in modules)

    for spec in reg.locks:
        assert exists(spec.module), f"lock {spec.name}: {spec.module}"
    for e in reg.cross_query_entries:
        assert exists(e.module), f"entry {e.func}: {e.module}"
    for p in reg.pairs:
        for m in p.modules:
            assert exists(m), f"pair {p.name}: {m}"
    # every ordered lock is a registered lock
    names = {s.name for s in reg.locks}
    for n in reg.lock_order:
        assert n in names, n


def test_docs_rule_table_matches_registry():
    """docs/static_analysis.md's rule table lists exactly RULES — the
    EVENT_LEVELS/CANONICAL_METRICS drift-lint pattern."""
    import re
    docs = (ROOT / "docs" / "static_analysis.md").read_text()
    rows = set(re.findall(r"^\|\s*`([a-z0-9-]+)`\s*\|", docs,
                          re.MULTILINE))
    expected = set(reg_mod.RULES)
    assert rows == expected, (
        f"docs/static_analysis.md rule table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_every_rule_family_is_fixture_proven():
    """Acceptance guard: each non-meta rule family has at least one
    fixture where it fires (the per-rule tests above pin the details —
    this keeps a NEW rule from landing without a fixture)."""
    fired = set()
    for fname in ("fx_locks.py", "fx_bounded_wait.py", "fx_threads.py",
                  "fx_trace.py", "fx_conf.py", "fx_accounting.py",
                  "fx_registry.py", "fx_dispatch.py", "fx_stage.py",
                  "fx_host_sync.py"):
        for f in run_fixture(fname).findings:
            fired.add(f.rule)
    non_meta = {rid for rid, m in reg_mod.RULES.items()
                if m.checker is not None}
    assert non_meta <= fired, sorted(non_meta - fired)


# -- THE tier-1 gate ---------------------------------------------------------

def test_whole_package_is_clean_or_baselined():
    """The CI gate (ISSUE 12 acceptance): the analyzer runs over the
    full package scan set in-process; every finding is either inline-
    suppressed with a justification or covered by a justified baseline
    entry; no stale baseline entries (fixes must shrink the file); no
    UNREVIEWED/empty baseline justifications."""
    report = contract_check.build_report()
    baseline = acore.load_baseline(contract_check.DEFAULT_BASELINE)
    new, stale, lint = acore.apply_baseline(report.sorted_findings(),
                                            baseline)
    assert new == [], "new contract findings:\n" + "\n".join(
        f.render() for f in new)
    assert stale == [], (
        "stale baseline entries (finding fixed — delete them): "
        f"{stale}")
    assert lint == [], "\n".join(f.render() for f in lint)
    # the escape hatches stay justified
    for _f, why, _line in report.suppressed:
        assert why.strip()
