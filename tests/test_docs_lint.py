"""Docs/registry drift lint (ISSUE 2 satellite): every conf key the
code uses resolves to the registry and is documented in docs/configs.md
(unless internal), and every additional_metrics() name is canonical and
unique — one name, one meaning, across the exec tree (reference
GpuMetric companion discipline).

ISSUE 12: the AST scanning (source discovery, conf-key literal walk,
unregistered-key and unregistered-event-kind detection) lives in
`spark_rapids_tpu.analysis` now — ONE rule registry. This file keeps
only the doc-TABLE assertions the analyzer doesn't own (a markdown
table matching a Python registry) and delegates every code walk to
`analysis.scan` / the `registry-drift` rules."""

import importlib
import re
import sys
from pathlib import Path

import pytest

from spark_rapids_tpu import analysis
from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.exec import base as exec_base

ROOT = Path(__file__).resolve().parents[1]


def test_conf_keys_in_code_are_registered_and_documented():
    """ONE walk: source discovery and the conf-key literal scan are the
    analyzer's (`analysis.scan` — the same scanner the
    `conf-key-registered` rule runs on, which the contract-check tier-1
    gate enforces with suppression/baseline support package-wide); this
    test derives both halves — registration and docs presence — from
    that single pass."""
    docs = (ROOT / "docs" / "configs.md").read_text()
    dynamic = cfg.RapidsConf._DYNAMIC_PREFIXES
    problems = []
    for path in analysis.default_source_files(ROOT):
        for key, lineno in analysis.conf_key_literals(path):
            where = f"{path.relative_to(ROOT)}:{lineno}"
            entry = cfg._REGISTRY.get(key)
            if entry is None:
                if not key.startswith(dynamic):
                    problems.append(f"{where}: {key} not in the config "
                                    "registry")
            elif not entry.internal and f"`{key}`" not in docs:
                problems.append(f"{where}: {key} missing from "
                                "docs/configs.md — run tools/gen_docs.py")
    assert not problems, "\n".join(problems)


def test_registry_docs_are_current():
    """docs/configs.md is exactly what generate_docs() renders — a
    stale file fails here, not in review."""
    assert (ROOT / "docs" / "configs.md").read_text() \
        == cfg.generate_docs(), "run tools/gen_docs.py"


def _all_exec_classes():
    pkg_dir = ROOT / "spark_rapids_tpu" / "exec"
    for py in sorted(pkg_dir.glob("*.py")):
        importlib.import_module(f"spark_rapids_tpu.exec.{py.stem}")

    def subclasses(cls):
        for c in cls.__subclasses__():
            yield c
            yield from subclasses(c)

    return sorted(set(subclasses(exec_base.TpuExec)),
                  key=lambda c: c.__name__)


def test_fault_point_registry_matches_docs():
    """docs/robustness.md's fault-point table lists exactly the points
    registered in faults.FAULT_POINTS (ISSUE 4: the same drift lint the
    conf registry gets) — and every registered point appears at its
    real call site somewhere in the package."""
    from spark_rapids_tpu import faults
    docs = (ROOT / "docs" / "robustness.md").read_text()
    documented = set(re.findall(r"^\|\s*`([a-z_]+\.[a-z_0-9]+)`\s*\|",
                                docs, re.MULTILINE))
    registered = set(faults.FAULT_POINTS)
    assert documented == registered, (
        f"docs/robustness.md fault table drifted: "
        f"missing={sorted(registered - documented)} "
        f"stale={sorted(documented - registered)}")
    # every point is wired: its name appears as a literal in a real
    # call site (outside faults.py itself)
    src = "".join(p.read_text()
                  for p in (ROOT / "spark_rapids_tpu").rglob("*.py")
                  if p.name != "faults.py")
    unwired = [p for p in registered if f'"{p}"' not in src]
    assert not unwired, f"registered fault points with no call site: {unwired}"


def test_breaker_tables_match_registry():
    """docs/robustness.md's circuit-breaker domain and state tables
    list exactly lifecycle.BREAKER_DOMAINS / BREAKER_STATES (ISSUE 6:
    the same drift lint the fault-point table gets). The check is
    scoped to the breaker section so taxonomy/fault tables elsewhere in
    the doc can't collide."""
    from spark_rapids_tpu.exec import lifecycle
    docs = (ROOT / "docs" / "robustness.md").read_text()
    m = re.search(r"## Degradation circuit breakers\n(.*?)(?:\n## |\Z)",
                  docs, re.DOTALL)
    assert m, "docs/robustness.md lost its circuit-breaker section"
    section = m.group(1)
    rows = set(re.findall(r"^\|\s*`([a-z_]+)`\s*\|", section,
                          re.MULTILINE))
    expected = set(lifecycle.BREAKER_DOMAINS) | set(
        lifecycle.BREAKER_STATES)
    assert rows == expected, (
        f"docs/robustness.md breaker tables drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_adaptive_decisions_table_matches_registry():
    """docs/robustness.md's adaptive-execution decision table lists
    exactly exec.adaptive.DECISIONS (ISSUE 19: the same drift lint the
    breaker-domain table gets), scoped to the adaptive section."""
    from spark_rapids_tpu.exec import adaptive
    docs = (ROOT / "docs" / "robustness.md").read_text()
    m = re.search(r"## Adaptive execution\n(.*?)(?:\n## |\Z)",
                  docs, re.DOTALL)
    assert m, "docs/robustness.md lost its adaptive-execution section"
    rows = set(re.findall(r"^\|\s*`([a-z_]+)`\s*\|", m.group(1),
                          re.MULTILINE))
    expected = set(adaptive.DECISIONS)
    assert rows == expected, (
        f"docs/robustness.md adaptive decision table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_workload_tables_match_registry():
    """docs/robustness.md's workload-governor admission-state and
    priority tables list exactly workload.ADMISSION_STATES /
    PRIORITIES (ISSUE 7: the same drift lint the breaker tables get),
    scoped to the governor section."""
    from spark_rapids_tpu.exec import workload
    docs = (ROOT / "docs" / "robustness.md").read_text()
    m = re.search(r"## Concurrent workload governor\n(.*?)(?:\n## |\Z)",
                  docs, re.DOTALL)
    assert m, "docs/robustness.md lost its workload-governor section"
    section = m.group(1)
    rows = set(re.findall(r"^\|\s*`([a-z_]+)`\s*\|", section,
                          re.MULTILINE))
    expected = set(workload.ADMISSION_STATES) | set(workload.PRIORITIES)
    assert rows == expected, (
        f"docs/robustness.md workload tables drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_stall_actions_table_matches_registry():
    """docs/robustness.md's stall-action table lists exactly
    speculation_shield.STALL_ACTIONS (ISSUE 20: the breaker-table drift
    discipline for the progress watchdog's closed action set), scoped
    to the shield section."""
    from spark_rapids_tpu.exec import speculation_shield
    docs = (ROOT / "docs" / "robustness.md").read_text()
    m = re.search(r"## Straggler & stall shield\n(.*?)(?:\n## |\Z)",
                  docs, re.DOTALL)
    assert m, "docs/robustness.md lost its straggler-shield section"
    # the action table nests inside the watchdog bullet, so rows carry
    # the bullet's indent
    rows = set(re.findall(r"^\s*\|\s*`([a-z][a-z-]*)`\s*\|", m.group(1),
                          re.MULTILINE))
    expected = set(speculation_shield.STALL_ACTIONS)
    assert rows == expected, (
        f"docs/robustness.md stall-action table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_robustness_event_kinds_are_registered():
    """Every event kind the robustness layer emits is in
    obs.events.EVENT_LEVELS (an unregistered kind silently defaults to
    MODERATE — fine at runtime, but the schema table must know it)."""
    from spark_rapids_tpu.obs import events
    for kind in ("fault_inject", "io_retry", "task_retry",
                 "integrity_fail", "pipeline_stuck", "spill_error",
                 "spill_writer_dead", "query_cancelled",
                 "task_retry_settle_error", "partition_recompute",
                 "breaker_open", "breaker_half_open", "breaker_close",
                 "peer_dead", "query_queued", "query_admitted",
                 "query_shed", "quota_spill"):
        assert kind in events.EVENT_LEVELS, kind
    docs = (ROOT / "docs" / "observability.md").read_text()
    for kind in events.EVENT_LEVELS:
        assert f"`{kind}`" in docs, (
            f"event kind {kind} missing from docs/observability.md")


def test_telemetry_series_table_matches_registry():
    """docs/observability.md's telemetry series table lists exactly
    obs.telemetry.SERIES (ISSUE 11: the same drift lint EVENT_LEVELS /
    CANONICAL_METRICS get), scoped to the telemetry section so other
    name tables in the doc can't collide."""
    from spark_rapids_tpu.obs import telemetry
    docs = (ROOT / "docs" / "observability.md").read_text()
    m = re.search(r"## Telemetry registry\n(.*?)(?:\n## |\Z)", docs,
                  re.DOTALL)
    assert m, "docs/observability.md lost its telemetry section"
    rows = set(re.findall(r"^\|\s*`([a-z_]+\.[a-z_0-9]+)`\s*\|",
                          m.group(1), re.MULTILINE))
    expected = set(telemetry.SERIES)
    assert rows == expected, (
        f"docs/observability.md telemetry table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_statistics_event_kinds_are_registered():
    """The runtime-statistics plane's event kinds are registered in
    EVENT_LEVELS (the ISSUE 4/6/7 pattern) — the docs-row half is
    covered by test_robustness_event_kinds_are_registered's full
    EVENT_LEVELS sweep."""
    from spark_rapids_tpu.obs import events
    for kind in ("exchange_stats", "telemetry_sample"):
        assert kind in events.EVENT_LEVELS, kind


def test_every_registered_conf_is_read():
    """Every conf `config.py` registers is read by the engine: its
    constant, its key, or the RapidsConf property that returns it
    occurs in at least one other file under spark_rapids_tpu/. An
    option nothing reads is a promise the engine does not keep
    (`spark.rapids.sql.explain` and
    `spark.rapids.sql.reader.batchSizeRows` were two, until PR 33)."""
    pkg = ROOT / "spark_rapids_tpu"
    config_py = pkg / "config.py"
    source = config_py.read_text()
    registered = re.findall(
        r"^([A-Z][A-Z0-9_]*) = conf_[a-z_]+\(\s*\"([^\"]+)\"",
        source, re.MULTILINE)
    assert len(registered) == len(cfg._REGISTRY), (
        "the scan lost a registration form", len(registered),
        len(cfg._REGISTRY))
    prop_of = {name: prop for prop, name in re.findall(
        r"def (\w+)\(self\):\n\s+return self\.get\((\w+)\)", source)}
    others = "\n".join(p.read_text() for p in sorted(pkg.rglob("*.py"))
                       if p != config_py)
    unread = [key for name, key in registered
              if not re.search(rf"\b{name}\b", others)
              and f'"{key}"' not in others
              and not (name in prop_of
                       and re.search(rf"\.{prop_of[name]}\b", others))]
    assert not unread, f"registered but read nowhere: {unread}"


def test_docs_cite_existing_files():
    """Every repo path that README.md, docs/*.md and the verify skill
    name (spark_rapids_tpu/, tools/, tests/, benchmarks/ with a .py,
    .md or .json suffix) exists: a document that cites a file which is
    gone, or was never written (three documents cited one such record
    file until PR 33), sends its reader after nothing."""
    documents = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md")),
                 ROOT / ".claude" / "skills" / "verify" / "SKILL.md"]
    cited = re.compile(
        r"(?<![\w./-])((?:spark_rapids_tpu|tools|tests|benchmarks)"
        r"/[\w./-]*\w\.(?:py|md|json))\b")
    missing = sorted(
        f"{doc.relative_to(ROOT)}: {path}"
        for doc in documents if doc.exists()
        for path in set(cited.findall(doc.read_text()))
        if not (ROOT / path).exists())
    assert not missing, "\n".join(missing)


def test_fusion_whitelist_table_matches_registry():
    """docs/perf.md's fusion-whitelist table lists exactly
    exec/stage_compiler.FUSABLE_OPS (ISSUE 14) — the tier-table drift
    lint pattern: an operator added to (or dropped from) the stage
    compiler without its docs row fails tier-1."""
    from spark_rapids_tpu.exec.stage_compiler import FUSABLE_OPS
    docs = (ROOT / "docs" / "perf.md").read_text()
    m = re.search(r"### Fusion whitelist\n(.*?)(?:\n#|\Z)", docs,
                  re.DOTALL)
    assert m, "docs/perf.md lost its fusion-whitelist table"
    rows = set(re.findall(r"^\|\s*`([A-Za-z0-9_]+Exec)`\s*\|",
                          m.group(1), re.MULTILINE))
    expected = set(FUSABLE_OPS)
    assert rows == expected, (
        f"docs/perf.md fusion-whitelist table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_additional_metrics_are_canonical_and_unique():
    classes = _all_exec_classes()
    assert len(classes) >= 20  # the walk actually found the exec tree
    problems = []
    valid_levels = {exec_base.ESSENTIAL, exec_base.MODERATE,
                    exec_base.DEBUG}
    for cls in classes:
        try:
            # the contract this lint enforces includes additional_metrics
            # being a static declaration (no self state)
            specs = list(cls.additional_metrics(None))
        except Exception as e:  # noqa: BLE001
            problems.append(f"{cls.__name__}.additional_metrics must be "
                            f"self-independent (got {type(e).__name__})")
            continue
        names = []
        for spec in specs:
            name, level = spec if isinstance(spec, tuple) \
                else (spec, exec_base.MODERATE)
            names.append(name)
            if name not in exec_base.CANONICAL_METRICS:
                problems.append(
                    f"{cls.__name__}: metric {name!r} is not canonical — "
                    "add it to exec.base.CANONICAL_METRICS or reuse an "
                    "existing name")
            if level not in valid_levels:
                problems.append(f"{cls.__name__}: metric {name!r} has "
                                f"invalid level {level!r}")
        if len(names) != len(set(names)):
            problems.append(f"{cls.__name__}: duplicate metric names "
                            f"{names}")
    assert not problems, "\n".join(problems)


def test_phase_table_matches_registry():
    """docs/observability.md's wall-clock phase table lists exactly
    obs.phase.PHASES (ISSUE 17: the same drift lint the telemetry
    series / event-kind tables get), scoped to the phase section."""
    from spark_rapids_tpu.obs import phase
    docs = (ROOT / "docs" / "observability.md").read_text()
    m = re.search(r"## Wall-clock phase attribution\n(.*?)(?:\n## |\Z)",
                  docs, re.DOTALL)
    assert m, "docs/observability.md lost its phase-attribution section"
    rows = set(re.findall(r"^\|\s*`([a-z][a-z-]*)`\s*\|", m.group(1),
                          re.MULTILINE))
    expected = set(phase.PHASES)
    assert rows == expected, (
        f"docs/observability.md phase table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_advisor_rules_table_matches_registry():
    """docs/robustness.md's advisor-rules table lists exactly the
    history_report.ADVISOR_RULES ids (ISSUE 17: the fault-point
    discipline for the advisor's closed rule registry)."""
    sys.path.insert(0, str(ROOT / "tools"))
    try:
        import history_report
    finally:
        sys.path.pop(0)
    docs = (ROOT / "docs" / "robustness.md").read_text()
    m = re.search(r"## Advisor rules\n(.*?)(?:\n## |\Z)", docs,
                  re.DOTALL)
    assert m, "docs/robustness.md lost its advisor-rules section"
    rows = set(re.findall(r"^\|\s*`([a-z][a-z-]*)`\s*\|", m.group(1),
                          re.MULTILINE))
    expected = {r.id for r in history_report.ADVISOR_RULES}
    assert rows == expected, (
        f"docs/robustness.md advisor table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")


def test_canonical_metrics_table_matches_registry():
    """docs/observability.md's canonical-metrics table has one row per
    exec.base.CANONICAL_METRICS name (ISSUE 17 satellite: the metric
    registry gets the same docs lint its consumers always had), scoped
    to the canonical-metrics section."""
    docs = (ROOT / "docs" / "observability.md").read_text()
    m = re.search(r"## Canonical metrics\n(.*?)(?:\n## |\Z)", docs,
                  re.DOTALL)
    assert m, "docs/observability.md lost its canonical-metrics section"
    rows = set(re.findall(r"^\|\s*`([a-zA-Z]+)`\s*\|", m.group(1),
                          re.MULTILINE))
    expected = set(exec_base.CANONICAL_METRICS)
    assert rows == expected, (
        f"docs/observability.md canonical-metrics table drifted: "
        f"missing={sorted(expected - rows)} "
        f"stale={sorted(rows - expected)}")
