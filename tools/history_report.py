"""Aggregate query-history capsules into a per-plan performance report,
diff two history dirs to rank regressions by the phase that moved, and
run the profiling advisor (ISSUE 17 tentpole part 3 — the reference's
qualification/profiling tool over Spark event logs, rebuilt over the
engine's own capsules).

Usage:
    python tools/history_report.py HISTORY_DIR [--top N]
                                   [--format text|json]
    python tools/history_report.py CUR_DIR --diff BASE_DIR

Each capsule is one JSONL line per finished governed query
(obs/history.py): plan fingerprint, the closed wall-clock phase ledger
(sum(phases) == wall_ns), essential metrics, worst exchange skew, and
the per-query deltas of the dispatch/shuffle/ici/upload/workload
process counters. Everything here joins on `fingerprint` — the
canonical plan identity — so two runs of the same workload compare
plan-by-plan without re-reading a single plan.

The advisor is a CLOSED rule registry (`ADVISOR_RULES`, lint-checked
against the docs/robustness.md advisor table like the fault-point and
event-kind registries): each rule looks at one per-fingerprint
aggregate, and fires with the evidence and the conf to turn. Rules
never guess — no evidence, no advice.

Stdlib only; importable (`read_capsules`, `aggregate`, `diff_report`,
`advise`) for tests and embedding.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
from typing import Any, Callable, Dict, List, NamedTuple, Optional

#: the closed phase set, mirrored from obs/phase.PHASES (stdlib-only
#: tool: no engine import; tests/test_history_report.py asserts the two
#: stay identical)
PHASES = (
    "admission-wait", "compile", "device-compute", "host-pack-serialize",
    "shuffle-io", "ici-collective", "spill-wait", "semaphore-wait",
    "pipeline-stall", "retry-backoff", "spec-wait", "plan", "scan-decode",
    "upload", "device-wait", "join-build", "group-agg", "sort", "plan-rerun",
    "other",
)


# ---------------------------------------------------------------------------
# capsule ingestion
# ---------------------------------------------------------------------------

def read_capsules(directory: str) -> List[Dict[str, Any]]:
    """Every parseable capsule under `directory` (all processes, all
    rotated members), oldest-first by timestamp. Truncated final lines
    (a SIGKILL'd process) are skipped, like profile_report."""
    out: List[Dict[str, Any]] = []
    bad = 0
    for path in sorted(_glob.glob(os.path.join(directory, "history-*.jsonl"))):
        with open(path) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    out.append(json.loads(ln))
                except ValueError:
                    bad += 1
    if bad:
        print(f"warning: skipped {bad} unparseable capsule line(s)",
              file=sys.stderr)
    out.sort(key=lambda c: c.get("ts_ms", 0))
    return out


def _pct(sorted_vals: List[int], pct: int) -> int:
    n = len(sorted_vals)
    if n == 0:
        return 0
    rank = max(1, -(-pct * n // 100))  # ceil, nearest-rank
    return sorted_vals[min(n, rank) - 1]


def _sum_family(agg: Dict[str, int], fam: Optional[Dict[str, Any]]) -> None:
    for k, v in (fam or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            agg[k] = agg.get(k, 0) + v


def aggregate(capsules: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Per-fingerprint roll-up: run count, wall p50/p95, per-phase mean
    ns, summed counter-family deltas, worst skew — the join table every
    other surface (report / diff / advisor) reads. Capsules without a
    fingerprint aggregate under "(none)"."""
    by_fp: Dict[str, Dict[str, Any]] = {}
    for c in capsules:
        fp = c.get("fingerprint") or "(none)"
        a = by_fp.get(fp)
        if a is None:
            a = by_fp[fp] = {
                "fingerprint": fp, "count": 0, "ok": 0, "walls": [],
                "phase_ns": {p: 0 for p in PHASES}, "phase_runs": 0,
                "rows": 0, "spill_bytes": 0, "mesh_devices": 1,
                "skew": None,
                "dispatch": {}, "shuffle": {}, "ici": {}, "upload": {},
                "workload": {}, "encoded": {}, "adaptive": {},
                "speculation": {},
            }
        a["count"] += 1
        a["ok"] += 1 if c.get("ok") else 0
        a["walls"].append(int(c.get("wall_ns", 0)))
        a["rows"] += c.get("rows", 0)
        a["spill_bytes"] += c.get("spill_bytes", 0)
        a["mesh_devices"] = max(a["mesh_devices"],
                                int(c.get("mesh_devices", 1)))
        ph = c.get("phases")
        if ph:
            a["phase_runs"] += 1
            for p in PHASES:
                a["phase_ns"][p] += int(ph.get(p, 0))
        sk = c.get("skew")
        if sk and (a["skew"] is None
                   or sk.get("ratio", 0) > a["skew"].get("ratio", 0)):
            a["skew"] = sk
        for fam in ("dispatch", "shuffle", "ici", "upload", "workload",
                    "encoded", "adaptive", "speculation"):
            _sum_family(a[fam], c.get(fam))
    for a in by_fp.values():
        walls = sorted(a.pop("walls"))
        a["p50_wall_ns"] = _pct(walls, 50)
        a["p95_wall_ns"] = _pct(walls, 95)
        runs = max(1, a["phase_runs"])
        a["phase_mean_ns"] = {p: v // runs
                              for p, v in a.pop("phase_ns").items()}
    return by_fp


# ---------------------------------------------------------------------------
# diff: rank regressions by the phase that moved
# ---------------------------------------------------------------------------

def diff_report(base: Dict[str, Dict[str, Any]],
                cur: Dict[str, Dict[str, Any]],
                ) -> List[Dict[str, Any]]:
    """Join two aggregates on fingerprint and rank by p50 wall-clock
    regression (worst first). Each row names the phase whose mean moved
    the most — the "WHERE did it get slower" answer --diff exists
    for. Improvements rank at the bottom with negative deltas."""
    rows: List[Dict[str, Any]] = []
    for fp, c in cur.items():
        b = base.get(fp)
        if b is None:
            continue
        delta = c["p50_wall_ns"] - b["p50_wall_ns"]
        phase_deltas = {
            p: c["phase_mean_ns"].get(p, 0) - b["phase_mean_ns"].get(p, 0)
            for p in PHASES}
        worst = max(phase_deltas, key=phase_deltas.__getitem__)
        rows.append({
            "fingerprint": fp,
            "base_p50_ns": b["p50_wall_ns"],
            "cur_p50_ns": c["p50_wall_ns"],
            "delta_ns": delta,
            "pct": round(100.0 * delta / b["p50_wall_ns"], 1)
            if b["p50_wall_ns"] else 0.0,
            "phase": worst,
            "phase_delta_ns": phase_deltas[worst],
            "phase_deltas": phase_deltas,
            "base_runs": b["count"], "cur_runs": c["count"],
        })
    rows.sort(key=lambda r: -r["delta_ns"])
    return rows


# ---------------------------------------------------------------------------
# the profiling advisor — closed rule registry
# ---------------------------------------------------------------------------

class AdvisorRule(NamedTuple):
    id: str                    # stable slug (docs table key)
    summary: str               # what the rule detects
    advice: str                # the knob/change to try
    check: Callable[[Dict[str, Any]], Optional[Dict[str, Any]]]
    # check(fp_aggregate) -> evidence dict when firing, else None


def _check_recompile_storm(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    d = a["dispatch"]
    storms = d.get("storms", 0)
    traces = d.get("traces", 0)
    # repeated runs of ONE fingerprint should trace once and then hit
    # the program cache; tracing on every run is a stage-cache miss
    # even when no single run was stormy enough to trip the detector
    retrace = a["count"] >= 2 and traces >= a["count"] \
        and d.get("dispatches", 0) > 0
    if storms <= 0 and not retrace:
        return None
    return {"storms": storms, "traces": traces,
            "dispatches": d.get("dispatches", 0), "runs": a["count"],
            "compile_mean_ns": a["phase_mean_ns"].get("compile", 0)}


def _check_per_buffer_upload(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    u = a["upload"]
    uploads = u.get("uploads", 0)
    per_buffer = u.get("per_buffer", 0)
    if uploads < 4 or per_buffer * 2 <= uploads:
        return None
    return {"uploads": uploads, "per_buffer": per_buffer,
            "share": round(per_buffer / uploads, 3)}


def _check_partition_skew(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    sk = a.get("skew")
    if not sk or sk.get("ratio", 0) < 4.0:
        return None
    ev = {"op": sk.get("op"), "ratio": sk.get("ratio"),
          "basis": sk.get("basis"), "partitions": sk.get("partitions"),
          "adaptive_consults": a["adaptive"].get("consults", 0),
          "skew_splits": a["adaptive"].get("skew_splits", 0)}
    # closed loop (ISSUE 19): when the capsule shows the adaptive
    # replanner never consulted, the remedy is the ONE-CONF fix — the
    # engine can split this partition itself from the same measured
    # statistics this rule fired on
    if ev["adaptive_consults"] == 0:
        ev["_advice"] = (
            "enable spark.rapids.tpu.adaptive.enabled — the runtime "
            "replanner splits the skewed partition into map-granular "
            "sub-reads from these same measured statistics")
    return ev


def _check_adaptive_demotion_storm(a: Dict[str, Any],
                                   ) -> Optional[Dict[str, Any]]:
    ad = a["adaptive"]
    demotions = ad.get("breaker_demotions", 0)
    if demotions <= 0:
        return None
    return {"breaker_demotions": demotions,
            "errors": ad.get("errors", 0),
            "skew_splits": ad.get("skew_splits", 0),
            "consults": ad.get("consults", 0)}


def _check_pipeline_stall(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    wall = a["p50_wall_ns"]
    stall = a["phase_mean_ns"].get("pipeline-stall", 0)
    if wall <= 0 or stall * 100 < wall * 30:
        return None
    return {"stall_mean_ns": stall, "p50_wall_ns": wall,
            "share": round(stall / wall, 3)}


def _check_ici_eligible(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if a["mesh_devices"] < 2:
        return None
    sh, ici = a["shuffle"], a["ici"]
    host_bytes = sh.get("bytes", 0)
    if host_bytes <= 0 or ici.get("rounds", 0) > 0 \
            or ici.get("fallbacks", 0) > 0:
        return None
    return {"mesh_devices": a["mesh_devices"],
            "host_shuffle_bytes": host_bytes,
            "ici_rounds": 0}


def _check_encoded_scan(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    en = a["encoded"]
    if en.get("cols_encoded", 0) > 0:
        return None
    sbytes = en.get("scan_string_bytes", 0)
    ubytes = a["upload"].get("bytes", 0)
    # fire only when the decoded string width is a material share of
    # what actually crossed the host->device link
    if sbytes <= 0 or ubytes <= 0 or sbytes * 2 < ubytes:
        return None
    return {"scan_string_bytes": sbytes, "upload_bytes": ubytes,
            "share": round(sbytes / ubytes, 3)}


def _check_straggler_prone(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    sp = a["speculation"]
    wall = a["p50_wall_ns"]
    wait = a["phase_mean_ns"].get("spec-wait", 0)
    denied, wins = sp.get("spec_denied", 0), sp.get("spec_wins", 0)
    # fire on either face of straggler exposure: wall-clock spent past
    # the measured p95 bound, or the in-flight budget repeatedly
    # refusing to race a straggler it detected
    slow = wall > 0 and wait * 100 >= wall * 10
    starved = denied > 0 and denied > wins
    if not (slow or starved):
        return None
    return {"spec_wait_mean_ns": wait, "p50_wall_ns": wall,
            "share": round(wait / wall, 3) if wall else 0.0,
            "spec_launched": sp.get("spec_launched", 0),
            "spec_wins": wins, "spec_denied": denied}


def _check_quota_spills(a: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    spills = a["workload"].get("quota_spills", 0)
    total = a.get("_total_quota_spills", spills)
    if spills <= 0 or spills * 2 <= total:
        return None
    return {"quota_spills": spills, "all_plans": total,
            "spill_bytes": a["spill_bytes"]}


#: the closed advisor registry — one row per rule in the
#: docs/robustness.md advisor table (lint: tests/test_docs_lint.py)
ADVISOR_RULES: tuple = (
    AdvisorRule(
        "recompile-storm",
        "a plan that recompiles across runs (dispatch storms, or fresh "
        "traces on every repeat of the same fingerprint) — the "
        "stage-program cache is missing",
        "check shape-bucket churn (coalesce batchSizeBytes) and "
        "spark.rapids.tpu.stage.fusion.enabled / "
        "stage.programCache.maxSites; the program_compile events name "
        "the unstable program",
        _check_recompile_storm),
    AdvisorRule(
        "per-buffer-upload",
        "the majority of host->device uploads took the per-buffer lane "
        "instead of one packed transfer",
        "read the upload events' lane/seam fields — typically a dtype "
        "the packer skips or "
        "spark.rapids.tpu.transfer.packedUpload.enabled off",
        _check_per_buffer_upload),
    AdvisorRule(
        "partition-skew",
        "one exchange partition carries >= 4x the median partition "
        "(max/median over exact per-partition totals)",
        "pre-split hot keys or broadcast the small side "
        "(spark.rapids.sql.broadcastSizeThreshold); the skew op names "
        "the exchange",
        _check_partition_skew),
    AdvisorRule(
        "adaptive-demotion-storm",
        "the adaptive replan lane repeatedly stood down (open "
        "`adaptive` breaker) while serving this plan — its decisions "
        "are misfiring, not helping",
        "raise spark.rapids.tpu.adaptive.skewedPartitionFactor so "
        "only extreme skew triggers replanning, or pin "
        "spark.rapids.tpu.adaptive.enabled off for this workload; the "
        "adaptive_demote events carry the failing decision",
        _check_adaptive_demotion_storm),
    AdvisorRule(
        "pipeline-stall",
        "the query spends >= 30% of wall-clock blocked on pipeline "
        "producers (consumer starvation)",
        "raise spark.rapids.tpu.pipeline.depth so producers run "
        "further ahead, or widen the slow producer stage",
        _check_pipeline_stall),
    AdvisorRule(
        "ici-eligible",
        "a multi-device mesh moved shuffle bytes over the host "
        "serialize lane with ZERO ICI collective rounds",
        "enable spark.rapids.tpu.shuffle.ici.enabled — the "
        "device-resident all-to-all lane keeps map output in HBM",
        _check_ici_eligible),
    AdvisorRule(
        "quota-spill-dominance",
        "one plan triggered the majority of the workload governor's "
        "quota-triggered self-spills",
        "raise spark.rapids.tpu.workload.memoryQuotaFraction or lower "
        "this plan's concurrency share — it is thrashing its own "
        "working set",
        _check_quota_spills),
    AdvisorRule(
        "straggler-prone",
        "the plan's shuffle reads repeatedly outlive their measured "
        "p95 straggler bound (spec-wait >= 10% of wall, or speculation "
        "denials outnumber wins)",
        "raise spark.rapids.tpu.shuffle.speculation.maxInFlight so "
        "denied stragglers get a duplicate raced instead of being "
        "waited out, and check the storage path feeding the shuffle "
        "dirs; if wins dominate, the duplicates are already saving "
        "the tail",
        _check_straggler_prone),
    AdvisorRule(
        "encoded-scan-eligible",
        "scans shipped decoded string bytes that dominate the "
        "host->device upload volume while keeping ZERO columns "
        "dictionary-encoded",
        "enable spark.rapids.tpu.scan.encoded.enabled — Parquet "
        "already ships these columns dictionary-encoded; the encoded "
        "lane uploads the i32 code lane plus the dictionary and "
        "materializes late through the gather engine",
        _check_encoded_scan),
)


def advise(agg: Dict[str, Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Run every rule over every per-fingerprint aggregate; one finding
    per (rule, fingerprint) that fires, evidence attached."""
    total_quota = sum(a["workload"].get("quota_spills", 0)
                     for a in agg.values())
    findings: List[Dict[str, Any]] = []
    for fp, a in sorted(agg.items()):
        a["_total_quota_spills"] = total_quota
        for rule in ADVISOR_RULES:
            ev = rule.check(a)
            if ev is not None:
                # a check may override the static remedy with a
                # sharper, evidence-specific one (the partition-skew
                # one-conf adaptive fix)
                advice = ev.pop("_advice", rule.advice)
                findings.append({"rule": rule.id, "fingerprint": fp,
                                 "summary": rule.summary,
                                 "advice": advice, "evidence": ev})
        del a["_total_quota_spills"]
    return findings


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _fmt_ns(ns: float) -> str:
    if abs(ns) < 1_000:
        return f"{ns:.0f}ns"
    if abs(ns) < 1_000_000:
        return f"{ns / 1_000:.1f}us"
    if abs(ns) < 1_000_000_000:
        return f"{ns / 1_000_000:.1f}ms"
    return f"{ns / 1_000_000_000:.2f}s"


def build_summary(directory: str, top: int = 20,
                  base_dir: Optional[str] = None) -> Dict[str, Any]:
    """The whole report as one JSON-able object (the --format json
    payload, and the import surface tests assert on)."""
    capsules = read_capsules(directory)
    agg = aggregate(capsules)
    out: Dict[str, Any] = {
        "dir": directory,
        "capsules": len(capsules),
        "plans": sorted(agg.values(),
                        key=lambda a: -a["p50_wall_ns"])[:top],
        "advisor": advise(agg),
    }
    if base_dir is not None:
        base_agg = aggregate(read_capsules(base_dir))
        out["base_dir"] = base_dir
        out["diff"] = diff_report(base_agg, agg)[:top]
    return out


def render_text(summary: Dict[str, Any]) -> str:
    lines: List[str] = []
    lines.append(f"query history: {summary['capsules']} capsule(s) "
                 f"in {summary['dir']}")
    lines.append("")
    lines.append("== plans (by p50 wall) ==")
    lines.append(f"{'fingerprint':<14} {'runs':>4} {'ok':>3} "
                 f"{'p50':>9} {'p95':>9} {'top phase':<18} {'share':>6}")
    for a in summary["plans"]:
        means = a["phase_mean_ns"]
        top_phase = max(means, key=means.__getitem__) if means else "-"
        share = means.get(top_phase, 0) / a["p50_wall_ns"] \
            if a["p50_wall_ns"] else 0.0
        lines.append(
            f"{a['fingerprint'][:12]:<14} {a['count']:>4} {a['ok']:>3} "
            f"{_fmt_ns(a['p50_wall_ns']):>9} "
            f"{_fmt_ns(a['p95_wall_ns']):>9} {top_phase:<18} "
            f"{share:>5.0%}")
    if "diff" in summary:
        lines.append("")
        lines.append(f"== regressions vs {summary['base_dir']} "
                     f"(by p50 delta) ==")
        lines.append(f"{'fingerprint':<14} {'base p50':>9} "
                     f"{'cur p50':>9} {'delta':>9} {'pct':>7} "
                     f"{'moved phase':<18}")
        for r in summary["diff"]:
            lines.append(
                f"{r['fingerprint'][:12]:<14} "
                f"{_fmt_ns(r['base_p50_ns']):>9} "
                f"{_fmt_ns(r['cur_p50_ns']):>9} "
                f"{_fmt_ns(r['delta_ns']):>9} {r['pct']:>6.1f}% "
                f"{r['phase']:<18} (+{_fmt_ns(r['phase_delta_ns'])})")
    lines.append("")
    findings = summary["advisor"]
    lines.append(f"== advisor: {len(findings)} finding(s) ==")
    for f in findings:
        lines.append(f"[{f['rule']}] plan {f['fingerprint'][:12]}")
        lines.append(f"    {f['summary']}")
        lines.append(f"    evidence: {json.dumps(f['evidence'])}")
        lines.append(f"    try: {f['advice']}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="history dir "
                    "(spark.rapids.tpu.history.dir)")
    ap.add_argument("--diff", metavar="BASE",
                    help="baseline history dir: rank per-plan p50 "
                    "regressions by the phase that moved")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)
    summary = build_summary(args.dir, top=args.top, base_dir=args.diff)
    if not summary["capsules"]:
        print("no capsules found "
              "(spark.rapids.tpu.history.enabled?)", file=sys.stderr)
        return 1
    if args.format == "json":
        json.dump(summary, sys.stdout, indent=2, default=str)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(render_text(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
