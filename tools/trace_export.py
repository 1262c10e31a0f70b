"""Convert a spark_rapids_tpu event log (JSONL, obs/events.py) into
Chrome trace format JSON — loadable in Perfetto (ui.perfetto.dev) or
chrome://tracing, so pipeline overlap, compile stalls and spill storms
are VISIBLE as a timeline instead of inferred from roll-up totals
(ISSUE 13 tentpole part 2).

Usage:
    python tools/trace_export.py EVENTS.jsonl [-o trace.json]
                                 [--query QID]

Given any member of a rotated log set (eventLog.maxBytes), the whole
set is read in rotation order. Stdlib only.

Mapping
-------
* One timeline TRACK per emitting thread — the `thread` field every
  event record carries (ISSUE 13 satellite): the consumer
  (MainThread), each `pipeline-*` producer, the `spill-writer`, the
  `multifile-read`/`shuffle-*` pool workers. Records from builds
  predating the field land on one `<unknown>` track.
* Operator executions become complete ("X") spans synthesized from
  `op_close` (ts - wall_ns .. ts). Wall time is INCLUSIVE of child
  time (the pull model), so parent/child operator spans nest exactly
  like the reference's NVTX ranges. With a DEBUG-level log, `op_batch`
  records additionally become per-batch spans one nesting level in.
* Pipeline stage stalls (`pipeline_wait` / `pipeline_full`) become
  spans on their emitting thread sized by the stall total.
* Point events — spills, faults, IO/OOM/task retries, integrity
  quarantines, program compiles, recompile storms, breaker/lifecycle
  transitions — become instant ("i") events on their thread's track.
* `telemetry_sample` records become counter ("C") tracks (HBM by
  tier, budget use, admission queue depth) so resource pressure reads
  directly under the spans that caused it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from profile_report import read_event_files  # noqa: E402

#: event kinds rendered as instants, with the fields worth carrying
#: into the args pane (everything else the record has rides along too)
INSTANT_KINDS = (
    "spill", "spill_error", "oom_retry", "io_retry", "task_retry",
    "fault_inject", "integrity_fail", "program_compile",
    "recompile_storm", "pipeline_stuck", "spill_writer_dead",
    "query_cancelled", "query_shed", "breaker_open",
    "breaker_half_open", "breaker_close", "partition_recompute",
    "quota_spill", "query_queued", "query_admitted", "peer_dead",
    "shuffle_write", "upload", "exchange_stats",
    "gather_stats", "dispatch_stats",
)

#: telemetry series promoted to counter tracks (a readable subset —
#: the full sample still lands in the args of its instant)
COUNTER_SERIES = (
    "hbm.device_bytes", "hbm.host_bytes", "budget.used_bytes",
    "workload.queue_depth", "sem.wait_ns", "queries.active",
)

PID = 1


def _us(ts_ns: int) -> float:
    return ts_ns / 1_000.0


class _Tids:
    """Stable tid per thread name; insertion order = first appearance,
    with MainThread pinned to tid 1 so the consumer track sorts first."""

    def __init__(self):
        self._by_name: Dict[str, int] = {}

    def get(self, name: Optional[str]) -> int:
        name = name or "<unknown>"
        if name == "MainThread":
            self._by_name.setdefault(name, 1)
        if name not in self._by_name:
            taken = set(self._by_name.values())
            n = 2
            while n in taken:
                n += 1
            self._by_name[name] = n
        return self._by_name[name]

    def metadata(self) -> List[Dict[str, Any]]:
        out = [{"ph": "M", "pid": PID, "tid": 0,
                "name": "process_name",
                "args": {"name": "spark_rapids_tpu"}}]
        for name, tid in sorted(self._by_name.items(),
                                key=lambda kv: kv[1]):
            out.append({"ph": "M", "pid": PID, "tid": tid,
                        "name": "thread_name", "args": {"name": name}})
        return out


def _span_args(e: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in e.items()
            if k not in ("kind", "ts_ns", "thread")}


def build_trace(events: List[Dict[str, Any]],
                query: Optional[int] = None) -> Dict[str, Any]:
    """Chrome trace JSON object ({"traceEvents": [...]}) from parsed
    event records. Tolerates logs from builds without the `thread`
    field (one merged track) and without the dispatch plane (no
    compile instants — everything else still renders)."""
    if query is not None:
        events = [e for e in events if e.get("query") == query]
    tids = _Tids()
    out: List[Dict[str, Any]] = []
    for e in events:
        kind = e.get("kind")
        ts = e.get("ts_ns")
        if kind is None or ts is None:
            continue
        tid = tids.get(e.get("thread"))
        if kind == "op_close":
            wall = int(e.get("wall_ns") or 0)
            out.append({
                "ph": "X", "pid": PID, "tid": tid,
                "name": str(e.get("op")),
                "ts": _us(ts - wall), "dur": wall / 1_000.0,
                "cat": "operator", "args": _span_args(e)})
        elif kind == "op_batch":
            wall = int(e.get("wall_ns") or 0)
            out.append({
                "ph": "X", "pid": PID, "tid": tid,
                "name": f"{e.get('op')}#batch",
                "ts": _us(ts - wall), "dur": wall / 1_000.0,
                "cat": "batch", "args": _span_args(e)})
        elif kind in ("pipeline_wait", "pipeline_full"):
            stall = int(e.get("wait_ns") or e.get("full_ns") or 0)
            out.append({
                "ph": "X", "pid": PID, "tid": tid,
                "name": f"{kind}:{e.get('stage')}",
                "ts": _us(ts - stall), "dur": stall / 1_000.0,
                "cat": "stall", "args": _span_args(e)})
        elif kind == "telemetry_sample":
            for series in COUNTER_SERIES:
                if series in e:
                    out.append({
                        "ph": "C", "pid": PID, "tid": 0,
                        "name": series, "ts": _us(ts),
                        "args": {"value": e[series]}})
        elif kind in INSTANT_KINDS:
            out.append({
                "ph": "i", "pid": PID, "tid": tid, "s": "t",
                "name": kind, "ts": _us(ts), "cat": "event",
                "args": _span_args(e)})
        elif kind in ("query_start", "query_end"):
            out.append({
                "ph": "i", "pid": PID, "tid": tid, "s": "p",
                "name": f"{kind}:{e.get('query')}", "ts": _us(ts),
                "cat": "query", "args": _span_args(e)})
    return {"traceEvents": tids.metadata() + out,
            "displayTimeUnit": "ms"}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log", help="events-*.jsonl file (obs/events.py); "
                               "a rotated set is read in order")
    ap.add_argument("-o", "--out", default=None,
                    help="output path (default: <log>.trace.json)")
    ap.add_argument("--query", type=int, default=None,
                    help="restrict to one query id")
    args = ap.parse_args(argv)
    events = read_event_files(args.log)
    trace = build_trace(events, query=args.query)
    out_path = args.out or (args.log + ".trace.json")
    with open(out_path, "w") as f:
        json.dump(trace, f)
    n_tracks = sum(1 for t in trace["traceEvents"]
                   if t.get("ph") == "M" and t["name"] == "thread_name")
    print(f"{out_path}: {len(trace['traceEvents'])} trace events, "
          f"{n_tracks} thread tracks — load in ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
