"""Render a spark_rapids_tpu event log (JSONL, obs/events.py) into a
top-N operator time/bytes table — the offline half of the query-profile
surface (ISSUE 2; reference analog: the qualification/profiling tool
over Spark event logs).

Usage:
    python tools/profile_report.py EVENTS.jsonl [--top N] [--query QID]
                                   [--format text|json]

Reads `op_close` spans (cumulative wall-ns / rows / batches per
operator instance), `op_batch` spans (per-batch bytes), and the
query/task events (spill, oom_retry, semaphore_acquire, exchange) and
prints one aggregated report. Wall-ns are INCLUSIVE of child time (the
pull model), so percentages are of the slowest root span, not a sum.

`--format json` (ISSUE 11 satellite) emits the SAME roll-ups as the
text report — top ops, pipeline overlap, gathers, shuffle writes,
uploads, robustness, workload, runtime statistics — as one JSON object
(`build_summary`), so CI and AQE tests assert on fields instead of
scraping text. Given any member of a rotated log set
(`events-<pid>-<n>.jsonl` + `.1.jsonl`, `.2.jsonl`, ... —
spark.rapids.tpu.eventLog.maxBytes), the whole set is read in rotation
order. Stdlib only — runs anywhere the log file lands.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional


def read_events(lines: Iterable[str]) -> List[Dict[str, Any]]:
    out = []
    bad = 0
    for ln in lines:
        ln = ln.strip()
        if not ln:
            continue
        try:
            out.append(json.loads(ln))
        except ValueError:
            # a SIGKILL'd process can leave a truncated final line; the
            # parseable prefix is exactly what a crash profile needs
            bad += 1
    if bad:
        print(f"warning: skipped {bad} unparseable line(s)",
              file=sys.stderr)
    return out


def rotated_set(path: str) -> List[str]:
    """All members of `path`'s rotated log set, in write order (base
    file first, then `.1.jsonl`, `.2.jsonl`, ...). A non-rotated log —
    or any file that does not match the rotation naming — returns just
    itself, so every existing caller keeps working."""
    m = re.fullmatch(r"(.*?)(?:\.(\d+))?\.jsonl", path)
    if m is None:
        return [path]
    base = m.group(1)
    members = [(0, f"{base}.jsonl")]
    for p in _glob.glob(_glob.escape(base) + ".*.jsonl"):
        mm = re.fullmatch(re.escape(base) + r"\.(\d+)\.jsonl", p)
        if mm:
            members.append((int(mm.group(1)), p))
    out = [p for _n, p in sorted(members) if os.path.exists(p)]
    return out or [path]


def read_event_files(path: str) -> List[Dict[str, Any]]:
    """Read `path`'s whole rotated set in order (ISSUE 11 satellite:
    a soak's rotated log renders as one report; a truncated final line
    in any member is tolerated)."""
    events: List[Dict[str, Any]] = []
    for p in rotated_set(path):
        with open(p) as f:
            events.extend(read_events(f))
    return events


def _fmt_ns(ns: float) -> str:
    if ns < 1_000:
        return f"{ns:.0f}ns"
    if ns < 1_000_000:
        return f"{ns / 1_000:.1f}us"
    if ns < 1_000_000_000:
        return f"{ns / 1_000_000:.1f}ms"
    return f"{ns / 1_000_000_000:.2f}s"


def _fmt_bytes(b: float) -> str:
    if b < (1 << 10):
        return f"{b:.0f}B"
    if b < (1 << 20):
        return f"{b / (1 << 10):.1f}KB"
    if b < (1 << 30):
        return f"{b / (1 << 20):.1f}MB"
    return f"{b / (1 << 30):.2f}GB"


def _worst_skew(xstats: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    return max(xstats, key=lambda e: e.get("skew_ratio") or 0, default=None)


def _dispatch_rollup(compiles: List[Dict[str, Any]],
                     storms: List[Dict[str, Any]],
                     dstats: List[Dict[str, Any]],
                     top: int) -> Dict[str, Any]:
    """The `dispatch` section of build_summary: program_compile /
    recompile_storm / dispatch_stats events aggregated by program label
    and by operator."""
    by_label: Dict[str, Dict[str, Any]] = {}
    for e in compiles:
        lab = e.get("label") or "?"
        agg = by_label.setdefault(lab, {"label": lab, "compiles": 0,
                                        "programs": 0, "compile_ns": 0,
                                        "trace_ns": 0})
        agg["compiles"] += 1
        agg["programs"] += 1 if e.get("first") else 0
        agg["compile_ns"] += e.get("compile_ns") or 0
        agg["trace_ns"] += e.get("trace_ns") or 0
    top_compile = sorted(by_label.values(),
                         key=lambda r: -r["compile_ns"])[:top]
    by_op: Dict[Any, Dict[str, Any]] = {}
    for e in dstats:
        key = (e.get("op"), e.get("op_id"))
        agg = by_op.setdefault(key, {"op": e.get("op"),
                                     "op_id": e.get("op_id"),
                                     "dispatches": 0, "batches": 0,
                                     "compile_ns": 0})
        agg["dispatches"] += e.get("dispatches") or 0
        agg["batches"] += e.get("batches") or 0
        agg["compile_ns"] += e.get("compile_ns") or 0
    for r in by_op.values():
        r["dispatches_per_batch"] = (
            round(r["dispatches"] / r["batches"], 4)
            if r["batches"] else None)
    top_rate = sorted(
        by_op.values(),
        key=lambda r: -(r["dispatches_per_batch"] or 0))[:top]
    return {
        "programs_compiled": len(compiles),
        "compile_ns": sum(e.get("compile_ns") or 0 for e in compiles),
        "trace_ns": sum(e.get("trace_ns") or 0 for e in compiles),
        "top_by_compile_ns": top_compile,
        "top_by_dispatches_per_batch": top_rate,
        "storms": [{"label": e.get("label"),
                    "bucket": e.get("bucket"),
                    "traces_in_window": e.get("traces_in_window"),
                    "window_ms": e.get("window_ms")} for e in storms],
    }


def build_summary(events: List[Dict[str, Any]], top: int = 10,
                  query: Optional[int] = None) -> Dict[str, Any]:
    """THE report data: every roll-up the text renderer prints, as one
    machine-readable dict (the `--format json` payload). build_report
    renders from this, so the two formats cannot drift."""
    if query is not None:
        events = [e for e in events if e.get("query") == query]

    # per-operator-instance aggregation
    ops: Dict[Any, Dict[str, Any]] = {}
    for e in events:
        kind = e.get("kind")
        if kind not in ("op_close", "op_batch"):
            continue
        key = (e.get("op"), e.get("op_id"))
        agg = ops.setdefault(key, {"op": e.get("op"),
                                   "op_id": e.get("op_id"),
                                   "wall_ns": 0, "rows": 0, "batches": 0,
                                   "bytes": 0})
        if kind == "op_close":
            agg["wall_ns"] += e.get("wall_ns") or 0
            agg["rows"] += e.get("rows") or 0
            agg["batches"] += e.get("batches") or 0
        else:
            agg["bytes"] += e.get("bytes") or 0

    rows = sorted(ops.values(), key=lambda r: -r["wall_ns"])
    total_ns = max((r["wall_ns"] for r in rows), default=0)
    top_ops = []
    for r in rows[:top]:
        row = dict(r)
        row["pct_root"] = round(100.0 * r["wall_ns"] / total_ns, 1) \
            if total_ns else 0.0
        top_ops.append(row)

    def count(kind) -> int:
        return sum(1 for e in events if e.get("kind") == kind)

    def total(kind, field) -> int:
        return sum(e.get(field) or 0 for e in events
                   if e.get("kind") == kind)

    def by(kind, field) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in events:
            if e.get("kind") == kind:
                k = e.get(field, "?") or "?"
                out[k] = out.get(k, 0) + 1
        return out

    fused = [e for e in events if e.get("kind") == "stage_fused"]
    compiles = [e for e in events if e.get("kind") == "program_compile"]
    storms = [e for e in events if e.get("kind") == "recompile_storm"]
    dstats = [e for e in events if e.get("kind") == "dispatch_stats"]
    writes = [e for e in events if e.get("kind") == "shuffle_write"]
    gstats = [e for e in events if e.get("kind") == "gather_stats"]
    ups = [e for e in events if e.get("kind") == "upload"]
    xstats = [e for e in events if e.get("kind") == "exchange_stats"]
    ici = [e for e in events if e.get("kind") == "ici_exchange"]
    ici_ok = [e for e in ici if not e.get("fallback")]
    escan = [e for e in events if e.get("kind") == "encoded_scan"]
    emat = [e for e in events if e.get("kind") == "encoded_materialize"]
    replans = [e for e in events if e.get("kind") == "adaptive_replan"]
    demotes = [e for e in events if e.get("kind") == "adaptive_demote"]
    waits = [e.get("wait_ms") or 0 for e in events
             if e.get("kind") == "query_admitted"]
    qphases = [e for e in events if e.get("kind") == "query_phases"]
    phase_ns: Dict[str, int] = {}
    for e in qphases:
        for p, v in (e.get("phases") or {}).items():
            phase_ns[p] = phase_ns.get(p, 0) + (v or 0)

    summary: Dict[str, Any] = {
        "events": len(events),
        # wall-clock phase attribution roll-up (ISSUE 17): one
        # query_phases record per governed query, each a closed ledger
        # (sum(phases) == wall_ns) — summed here so a whole log answers
        # "where did the wall-clock go" in one table. Zero-tolerant:
        # pre-phase logs report zeros and print nothing.
        "phases": {
            "queries": len(qphases),
            "wall_ns": sum(e.get("wall_ns") or 0 for e in qphases),
            "by_phase": phase_ns},
        "queries": sorted({e.get("query") for e in events
                           if e.get("query") is not None}),
        "completed": count("query_end"),
        "top_ops": top_ops,
        "operators": len(rows),
        "spills": {"count": count("spill"),
                   "bytes": total("spill", "bytes")},
        "oom_retries": count("oom_retry"),
        "semaphore_wait_ns": total("semaphore_acquire", "wait_ns"),
        "pipeline": {"stages": count("pipeline_wait"),
                     "consumer_wait_ns": total("pipeline_wait",
                                               "wait_ns"),
                     "producer_full_ns": total("pipeline_full",
                                               "full_ns")},
        "exchange_bytes": total("exchange", "bytes"),
        "shuffle_writes": {
            "maps": len(writes),
            "bytes": total("shuffle_write", "bytes"),
            "frames": total("shuffle_write", "frames"),
            "device_partitioned": sum(1 for e in writes
                                      if e.get("lane") == "device"),
            "pack_ns": total("shuffle_write", "pack_ns"),
            "serialize_ns": total("shuffle_write", "serialize_ns"),
            "io_ns": total("shuffle_write", "io_ns")},
        # ICI shuffle roll-up (ISSUE 16): device-resident all-to-all
        # exchange rounds — bytes that never touched the host, the
        # negotiated slot caps and grid fill (the sizing methodology's
        # feedback signal), and how many streams degraded to the host
        # serialize lane. Zero-tolerant: pre-ICI logs report zeros.
        "ici_shuffle": {
            "rounds": len(ici_ok),
            "batches": sum(e.get("batches") or 0 for e in ici_ok),
            "rows": sum(e.get("rows") or 0 for e in ici_ok),
            "bytes": sum(e.get("bytes") or 0 for e in ici_ok),
            "collective_ns": sum(e.get("collective_ns") or 0
                                 for e in ici_ok),
            "max_slot_cap": max((e.get("slot_cap") or 0
                                 for e in ici_ok), default=0),
            "avg_fill": round(sum(e.get("fill") or 0 for e in ici_ok)
                              / len(ici_ok), 4) if ici_ok else 0.0,
            "fallbacks": sum(1 for e in ici if e.get("fallback"))},
        # encoded-execution roll-up (ISSUE 18): scan batches that kept
        # columns dictionary-encoded, the code/dictionary byte split,
        # the eager-decode bytes the lane avoided building, and where
        # the late materializations happened (a healthy plan decodes
        # only at output-level seams). Zero-tolerant: pre-encoded logs
        # report zeros.
        "encoded": {
            "scan_batches": len(escan),
            "cols_encoded": sum(e.get("cols_encoded") or 0
                                for e in escan),
            "codes_bytes": sum(e.get("codes_bytes") or 0
                               for e in escan),
            "dict_bytes": sum(e.get("dict_bytes") or 0 for e in escan),
            "decoded_bytes_avoided": sum(
                e.get("decoded_bytes_avoided") or 0 for e in escan),
            "materializations": sum(e.get("cols") or 0 for e in emat),
            "materialize_seams": by("encoded_materialize", "seam")},
        "plan_fallbacks": (count("plan_fallback")
                           + count("plan_not_on_tpu")),
        "robustness": {
            "injected_faults": by("fault_inject", "point"),
            "io_retries": count("io_retry"),
            "task_retries": count("task_retry"),
            "integrity_quarantines": count("integrity_fail"),
            "watchdog_trips": (count("pipeline_stuck")
                               + count("spill_writer_dead"))},
        "lifecycle": {
            "cancellations": by("query_cancelled", "phase"),
            "breaker": {"open": count("breaker_open"),
                        "half_open": count("breaker_half_open"),
                        "close": count("breaker_close")},
            "partition_recomputes": count("partition_recompute")},
        # straggler-shield roll-up (ISSUE 20): stall episodes by the
        # configured action, speculative sub-read races by winner, hang
        # bounds tripped by breaker domain, and dead-peer map-output
        # invalidations. Zero-tolerant: pre-shield logs print nothing.
        "speculation": {
            "stalls": by("query_stalled", "action"),
            "spec_fetches": count("speculative_fetch"),
            "spec_winners": by("speculative_fetch", "winner"),
            "dispatch_timeouts": by("dispatch_timeout", "domain"),
            "outputs_invalidated": count("map_output_invalidated")},
        "workload": {
            "admissions": count("query_admitted"),
            "queued": count("query_queued"),
            "max_wait_ms": max(waits) if waits else 0,
            "sheds": by("query_shed", "reason"),
            "quota_spills": count("quota_spill")},
        # dispatch/compile roll-up (ISSUE 13): what the per-operator
        # program model costs — how many programs compiled, which
        # labels paid the most compile wall-clock, which stages issue
        # the most dispatches per batch (the whole-stage-compilation
        # baseline), and any recompile storms. Logs from builds without
        # the dispatch plane simply report zeros/empty lists.
        "dispatch": _dispatch_rollup(compiles, storms, dstats, top),
        # whole-stage-compilation roll-up (ISSUE 14): fused-stage
        # executions, operators absorbed, and the dispatches saved vs
        # the per-op baseline (one program per absorbed op per input
        # batch is what the fused program replaced). Zero-tolerant:
        # logs from pre-fusion builds report zeros and print nothing.
        "fused_stages": {
            "executions": len(fused),
            "ops_absorbed": sum(e.get("ops") or 0 for e in fused),
            "batches": sum(e.get("batches") or 0 for e in fused),
            "dispatches": sum(e.get("dispatches") or 0 for e in fused),
            "dispatches_saved": sum(
                max((e.get("ops") or 0) * (e.get("batches") or 0)
                    - (e.get("dispatches") or 0), 0) for e in fused),
            "donated_bytes": max((e.get("donated_bytes") or 0
                                  for e in fused), default=0),
            "by_label": sorted({e.get("label") or "?" for e in fused}),
        },
        "gathers": {"count": sum(e.get("count") or 0 for e in gstats),
                    "records": len(gstats),
                    "packed": sum(e.get("packed") or 0 for e in gstats),
                    "bytes": sum(e.get("bytes") or 0 for e in gstats)},
        "uploads": {
            "batches": len(ups),
            "packed": sum(1 for e in ups if e.get("lane") == "packed"),
            "per_buffer": sum(1 for e in ups
                              if e.get("lane") != "packed"),
            "transfers": sum(e.get("transfers") or 0 for e in ups),
            "bytes": sum(e.get("bytes") or 0 for e in ups),
            "pack_ns": sum(e.get("pack_ns") or 0 for e in ups)},
        # runtime-statistics roll-up (ISSUE 11): per-exchange skew +
        # distribution records — worst skew leads, it is the AQE signal.
        # Exchanges may compute skew on different bases (rows vs bytes),
        # so the headline carries the winning exchange's basis alongside.
        "statistics": {
            "exchanges": len(xstats),
            "maps": sum(e.get("maps") or 0 for e in xstats),
            "max_skew_ratio": ((_worst_skew(xstats) or {}).get("skew_ratio")
                               or 0),
            "max_skew_basis": (_worst_skew(xstats) or {}).get("skew_basis"),
            "p95_map_output_bytes": max(
                (e.get("p95_map_output_bytes") or 0 for e in xstats),
                default=0),
            "telemetry_samples": count("telemetry_sample"),
            "per_exchange": [
                {"exec": e.get("exec"), "op_id": e.get("op_id"),
                 "partitions": e.get("partitions"),
                 "maps": e.get("maps"), "rows": e.get("rows"),
                 "bytes": e.get("bytes"),
                 "skew_ratio": e.get("skew_ratio"),
                 "skew_basis": e.get("skew_basis"),
                 "p95_partition_bytes": e.get("p95_partition_bytes"),
                 "p95_map_output_bytes": e.get("p95_map_output_bytes")}
                for e in xstats]},
        # adaptive-execution roll-up (ISSUE 19): what the runtime
        # replanner DID with the measured statistics above — decision
        # counts by kind plus each decision's evidence record
        "adaptive": {
            "replans": len(replans),
            "demotes": len(demotes),
            "skew_splits": sum(1 for e in replans
                               if e.get("decision") == "skew_split"),
            "broadcast_demotes": sum(
                1 for e in demotes
                if e.get("decision") == "broadcast_demote"),
            "single_build_converts": sum(
                1 for e in replans
                if e.get("decision") == "single_build_convert"),
            "partition_coalesces": sum(
                1 for e in replans
                if e.get("decision") == "partition_coalesce"),
            "batch_right_sizes": sum(
                1 for e in replans
                if e.get("decision") == "batch_right_size"),
            "lane_demotions": sum(1 for e in demotes
                                  if e.get("decision") == "lane"),
            "decisions": [
                {k: e.get(k) for k in
                 ("kind", "exec", "op_id", "decision", "reason",
                  "partition", "bytes", "measured_bytes", "threshold",
                  "median_bytes", "subs", "max_sub_bytes", "basis",
                  "reads", "target_bytes", "prev_target", "new_target")
                 if e.get(k) is not None}
                for e in replans + demotes]},
    }
    return summary


def build_report(events: List[Dict[str, Any]], top: int = 10,
                 query: Optional[int] = None) -> str:
    """Text renderer over build_summary — same data, human form."""
    s = build_summary(events, top=top, query=query)
    lines: List[str] = []
    lines.append(f"event log: {s['events']} events, "
                 f"{len(s['queries'])} queries "
                 f"({s['completed']} completed)")

    rows = s["top_ops"]
    if rows:
        lines.append("")
        lines.append(f"top {min(top, s['operators'])} operators by "
                     "inclusive wall time:")
        hdr = (f"{'#':>3} {'operator':<28} {'id':>4} {'time':>10} "
               f"{'%root':>6} {'rows':>12} {'batches':>8} {'bytes':>10}")
        lines.append(hdr)
        lines.append("-" * len(hdr))
        for i, r in enumerate(rows, 1):
            lines.append(
                f"{i:>3} {r['op']:<28} "
                f"{r['op_id'] if r['op_id'] is not None else '-':>4} "
                f"{_fmt_ns(r['wall_ns']):>10} {r['pct_root']:>5.1f}% "
                f"{r['rows']:>12} {r['batches']:>8} "
                f"{_fmt_bytes(r['bytes']):>10}")

    # phase attribution (ISSUE 17): the summed closed ledgers — every
    # governed query's wall partitioned, shares of the summed wall
    ph = s["phases"]
    if ph["queries"]:
        lines.append("")
        lines.append(f"wall-clock phases ({ph['queries']} governed "
                     f"quer{'y' if ph['queries'] == 1 else 'ies'}, "
                     f"{_fmt_ns(ph['wall_ns'])} total):")
        wall = ph["wall_ns"] or 1
        for p, v in sorted(ph["by_phase"].items(),
                           key=lambda kv: -kv[1]):
            if v:
                lines.append(f"    {p:<20} {_fmt_ns(v):>10} "
                             f"{100.0 * v / wall:>5.1f}%")

    extras = []
    if s["spills"]["count"]:
        extras.append(f"spills: {s['spills']['count']} "
                      f"({_fmt_bytes(s['spills']['bytes'])})")
    if s["oom_retries"]:
        extras.append(f"oom retries: {s['oom_retries']}")
    if s["semaphore_wait_ns"]:
        extras.append(f"semaphore wait: "
                      f"{_fmt_ns(s['semaphore_wait_ns'])}")
    pipe = s["pipeline"]
    if pipe["stages"]:
        extras.append(
            f"pipeline stages: {pipe['stages']} (consumer stalled "
            f"{_fmt_ns(pipe['consumer_wait_ns'])} on empty, producer "
            f"stalled {_fmt_ns(pipe['producer_full_ns'])} on full)")
    if s["exchange_bytes"]:
        extras.append(f"exchange bytes: "
                      f"{_fmt_bytes(s['exchange_bytes'])}")
    # shuffle-write roll-up (ISSUE 9): write time split pack (device
    # partition + packed D2H) / serialize / file IO, byte and frame
    # totals, and how many maps rode the device-partition lane
    sw = s["shuffle_writes"]
    if sw["maps"]:
        extras.append(
            f"shuffle writes: {sw['maps']} maps "
            f"({_fmt_bytes(sw['bytes'])} in {sw['frames']} frames; "
            f"{sw['device_partitioned']} device-partitioned; pack "
            f"{_fmt_ns(sw['pack_ns'])}, serialize "
            f"{_fmt_ns(sw['serialize_ns'])}, io "
            f"{_fmt_ns(sw['io_ns'])})")
    # ICI shuffle roll-up (ISSUE 16): the host-serialize collapse is
    # the optimization, so a pod round reads this line right under the
    # shuffle-write (host lane) one
    ic = s["ici_shuffle"]
    if ic["rounds"] or ic["fallbacks"]:
        extras.append(
            f"ici shuffle: {ic['rounds']} collective round(s) "
            f"({ic['batches']} map batches, {ic['rows']} rows, "
            f"{_fmt_bytes(ic['bytes'])} device-to-device in "
            f"{_fmt_ns(ic['collective_ns'])}; slot cap "
            f"{ic['max_slot_cap']}, fill {ic['avg_fill']:.2f}; "
            f"{ic['fallbacks']} host-lane fallback(s))")
    # encoded-execution roll-up (ISSUE 18): the decode-avoided bytes
    # are the optimization, so a round reads this line next to the
    # uploads one
    en = s["encoded"]
    if en["scan_batches"] or en["materializations"]:
        seams = ", ".join(f"{k}:{n}" for k, n in
                          sorted(en["materialize_seams"].items()))
        extras.append(
            f"encoded columns: {en['cols_encoded']} across "
            f"{en['scan_batches']} scan batch(es) "
            f"({_fmt_bytes(en['codes_bytes'])} codes + "
            f"{_fmt_bytes(en['dict_bytes'])} dictionaries, "
            f"{_fmt_bytes(en['decoded_bytes_avoided'])} eager decode "
            f"avoided; {en['materializations']} late "
            f"materialization(s){' at ' + seams if seams else ''})")
    if s["plan_fallbacks"]:
        extras.append(f"plan fallback/why-not records: "
                      f"{s['plan_fallbacks']}")
    # robustness roll-up (docs/robustness.md): how much chaos the run
    # absorbed, and at which recovery layer
    rob = s["robustness"]
    if rob["injected_faults"]:
        n_inject = sum(rob["injected_faults"].values())
        detail = ", ".join(f"{p}:{n}" for p, n
                           in sorted(rob["injected_faults"].items()))
        extras.append(f"injected faults: {n_inject} ({detail})")
    if rob["io_retries"]:
        extras.append(f"io retries: {rob['io_retries']}")
    if rob["task_retries"]:
        extras.append(f"task re-executions: {rob['task_retries']}")
    # lifecycle-governor roll-up (ISSUE 6): cancellations by phase,
    # breaker transitions, and which recovery lane paid for failures
    lc = s["lifecycle"]
    if lc["cancellations"]:
        n_cancel = sum(lc["cancellations"].values())
        detail = ", ".join(f"{p}:{n}" for p, n
                           in sorted(lc["cancellations"].items()))
        extras.append(f"query cancellations: {n_cancel} ({detail})")
    br = lc["breaker"]
    if br["open"] or br["half_open"] or br["close"]:
        extras.append(f"breaker trips: {br['open']} open, "
                      f"{br['half_open']} half-open, "
                      f"{br['close']} close")
    # only when the partition lane actually engaged — the whole-plan
    # count already prints as "task re-executions" above, and repeating
    # it alone would state the same figure twice
    if lc["partition_recomputes"]:
        extras.append(f"recovery lanes: {lc['partition_recomputes']} "
                      f"partition-granular recompute(s), "
                      f"{rob['task_retries']} whole-plan "
                      "re-execution(s)")
    # straggler-shield roll-up (ISSUE 20): reads right under the
    # recovery lanes it feeds — a stalled/straggling run shows WHERE
    # the shield intervened next to what the retry lanes then paid
    sp = s["speculation"]
    if sp["stalls"]:
        n_stall = sum(sp["stalls"].values())
        detail = ", ".join(f"{a}:{n}" for a, n
                           in sorted(sp["stalls"].items()))
        extras.append(f"query stalls: {n_stall} ({detail})")
    if sp["spec_fetches"]:
        w = sp["spec_winners"]
        extras.append(
            f"speculative sub-reads: {sp['spec_fetches']} "
            f"({w.get('spec', 0)} spec won, "
            f"{w.get('primary', 0)} primary won)")
    if sp["dispatch_timeouts"]:
        n_to = sum(sp["dispatch_timeouts"].values())
        detail = ", ".join(f"{d}:{n}" for d, n
                           in sorted(sp["dispatch_timeouts"].items()))
        extras.append(f"dispatch hang bounds tripped: {n_to} ({detail})")
    if sp["outputs_invalidated"]:
        extras.append(f"dead-peer map outputs invalidated: "
                      f"{sp['outputs_invalidated']}")
    # workload-governor roll-up (ISSUE 7): admission flow, sheds by
    # reason, and quota-triggered self-spills
    wl = s["workload"]
    if wl["admissions"] or wl["queued"] or wl["sheds"]:
        extras.append(
            f"workload admissions: {wl['admissions']} "
            f"({wl['queued']} queued, max wait {wl['max_wait_ms']}ms)")
    if wl["sheds"]:
        n_shed = sum(wl["sheds"].values())
        detail = ", ".join(f"{r}:{n}" for r, n
                           in sorted(wl["sheds"].items()))
        extras.append(f"queries shed: {n_shed} ({detail})")
    if wl["quota_spills"]:
        extras.append(f"quota spills: {wl['quota_spills']} "
                      f"(over-share queries spilled their own entries)")
    if rob["integrity_quarantines"]:
        extras.append(f"integrity quarantines: "
                      f"{rob['integrity_quarantines']}")
    if rob["watchdog_trips"]:
        extras.append(f"watchdog trips: {rob['watchdog_trips']}")
    # dispatch/compile roll-up (ISSUE 13): compile spend by program
    # label and the per-stage dispatch rate the whole-stage-compilation
    # work must collapse; absent entirely for pre-dispatch-plane logs
    dp = s["dispatch"]
    if dp["programs_compiled"]:
        extras.append(
            f"program compiles: {dp['programs_compiled']} "
            f"(compile {_fmt_ns(dp['compile_ns'])}, trace "
            f"{_fmt_ns(dp['trace_ns'])})")
        worst = dp["top_by_compile_ns"][:3]
        if worst:
            detail = ", ".join(
                f"{r['label']}:{_fmt_ns(r['compile_ns'])}"
                for r in worst)
            extras.append(f"  top compile cost: {detail}")
    rate = [r for r in dp["top_by_dispatches_per_batch"]
            if r["dispatches_per_batch"]][:3]
    if rate:
        detail = ", ".join(
            f"{r['op']}#{r['op_id']}:{r['dispatches_per_batch']}"
            for r in rate)
        extras.append(f"dispatches/batch (top stages): {detail}")
    if dp["storms"]:
        detail = ", ".join(
            f"{r['label']}({r['traces_in_window']} traces/"
            f"{r['window_ms']}ms)" for r in dp["storms"][:3])
        extras.append(f"RECOMPILE STORMS: {len(dp['storms'])} "
                      f"({detail})")
    # fused-stage roll-up (ISSUE 14): how much per-operator dispatch
    # overhead whole-stage compilation collapsed; absent on pre-fusion
    # logs
    fs = s["fused_stages"]
    if fs["executions"]:
        extras.append(
            f"fused stages: {fs['executions']} execution(s) "
            f"({fs['ops_absorbed']} ops absorbed, {fs['dispatches']} "
            f"dispatches over {fs['batches']} batches — "
            f"~{fs['dispatches_saved']} saved vs per-op; donated "
            f"state {_fmt_bytes(fs['donated_bytes'])})")
    # gather-engine roll-up (ISSUE 8): materializing row gathers per
    # wired operator — the count drop IS the optimization, so a bench
    # round reads it next to the pipeline/workload lines
    g = s["gathers"]
    if g["records"]:
        extras.append(
            f"gathers: {g['count']} ({g['packed']} packed rows, "
            f"~{_fmt_bytes(g['bytes'])} moved)")
    # upload-engine roll-up (ISSUE 10): host->device ingest — the
    # transfer-count drop (one per batch vs one per buffer) is the
    # optimization, so a round reads it next to the gather line
    u = s["uploads"]
    if u["batches"]:
        extras.append(
            f"uploads: {u['batches']} batches ({u['packed']} packed, "
            f"{u['per_buffer']} per-buffer; {u['transfers']} h2d "
            f"transfers, {_fmt_bytes(u['bytes'])}, pack "
            f"{_fmt_ns(u['pack_ns'])})")
    # runtime-statistics roll-up (ISSUE 11): the exchange skew line an
    # AQE round (ROADMAP 4) reads first
    st = s["statistics"]
    if st["exchanges"]:
        basis = f" (by {st['max_skew_basis']})" if st.get("max_skew_basis") else ""
        extras.append(
            f"statistics: {st['exchanges']} exchange(s), "
            f"{st['maps']} map outputs; max partition skew ratio "
            f"{st['max_skew_ratio']:.2f}{basis}, p95 map output "
            f"{_fmt_bytes(st['p95_map_output_bytes'])}")
    if st["telemetry_samples"]:
        extras.append(f"telemetry samples: {st['telemetry_samples']}")
    # adaptive-execution roll-up (ISSUE 19): what the runtime replanner
    # did with those measured statistics — reads right under the skew
    # line it acted on
    ad = s["adaptive"]
    if ad["replans"] or ad["demotes"]:
        extras.append(
            f"adaptive decisions: {ad['skew_splits']} skew split(s), "
            f"{ad['broadcast_demotes']} broadcast demotion(s), "
            f"{ad['single_build_converts']} single-build conversion(s), "
            f"{ad['partition_coalesces']} coalesce(s), "
            f"{ad['batch_right_sizes']} batch right-sizing(s)"
            + (f", {ad['lane_demotions']} lane stand-down(s)"
               if ad["lane_demotions"] else ""))
    if extras:
        lines.append("")
        lines.extend(extras)
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log", help="events-*.jsonl file (obs/events.py); "
                               "a rotated set is read in order")
    ap.add_argument("--top", type=int, default=10,
                    help="operators to show (default 10)")
    ap.add_argument("--query", type=int, default=None,
                    help="restrict to one query id")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text",
                    help="text table (default) or the machine-readable "
                         "summary JSON")
    args = ap.parse_args(argv)
    events = read_event_files(args.log)
    if args.format == "json":
        print(json.dumps(build_summary(events, top=args.top,
                                       query=args.query), indent=2))
    else:
        print(build_report(events, top=args.top, query=args.query))
    return 0


if __name__ == "__main__":
    sys.exit(main())
