"""One run of one cell: data from the seed, session, warm-up, the measured
window, the device's memory, the proofs, and only then the plain reference
and the comparison that decides `correct`."""

import math
import os
import shutil
import sys
import time

from . import datagen, observe, proofs, trace as trace_lib, window as window_lib
from .manifest import Manifest, apply_rehearsal
from .peaks import peaks_for


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_record(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; jax selected "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); jax found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip; 0 where the backend reports none (CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def warm_up(issue, max_collects: int = 6) -> int:
    """One compiling `collect()`, then warm ones until one compiles nothing.
    Returns the number of collects."""
    from spark_rapids_tpu.obs import dispatch
    for n in range(1, max_collects + 1):
        before = dispatch.counters()["traces"]
        issue()
        if dispatch.counters()["traces"] == before:
            return n
    raise RuntimeError(f"still compiling after {max_collects} collects")


def check_window(win, ref_mod, answer, limits: dict) -> dict:
    """{number: {"value", "limit"}}: for each number the worst over every
    query the window finished, plus the queries that never answered."""
    worst = {k: 0 for k in limits}
    for q in win.completed:
        for k, v in ref_mod.compare(q.result, answer).items():
            # a number that is not finite is over any limit (`max` would
            # drop a NaN)
            worst[k] = max(worst[k], v) if math.isfinite(v) else math.inf
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    checks["unanswered"] = {"value": len(win.queries) - len(win.completed),
                            "limit": 0}
    return checks


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _say(t_process: float, what: str) -> None:
    """Progress on standard error, so that a run cut short says how far it
    got."""
    print(f"bench +{time.perf_counter() - t_process:.1f}s {what}",
          file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest: Manifest = None, require_tpu: bool = True,
             rehearse: bool = False, t_process: float = None,
             keep_trace: str = None) -> dict:
    """The whole run; returns the result line as a dict (`checks` last)."""
    t_process = time.perf_counter() if t_process is None else t_process
    manifest = manifest or Manifest()
    cell = manifest.cell(workload)
    cfg = manifest.config(cell["config"])
    if rehearse:
        cfg = apply_rehearsal(cfg)
    traffic = manifest.traffic(cell["traffic"])
    e2e = manifest.metrics_of(workload, "end_to_end")
    per_layer = manifest.metrics_of(workload, "per_layer")
    ref_mod = manifest.config_module(cfg, "reference")
    query_mod = manifest.config_module(cfg, "query")

    device = device_record(require_tpu, int(cell["chips"]))
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import lifecycle

    work = os.path.join(manifest.root, ".bench_work", workload)
    shutil.rmtree(work, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        tables = ref_mod.generate(seed, cfg)
        paths = datagen.write_tables(os.path.join(work, "data"), tables,
                                     cfg["schema"], cfg["layout"])
        data_s = time.perf_counter() - t0
        _say(t_process, f"data written in {data_s:.1f}s; warming up")

        sess = TpuSession(dict(cfg.get("session_conf", {})))
        lifecycle0 = lifecycle.counters()
        snap0 = observe.snapshot()

        def issue():
            return query_mod.build(sess, paths, cfg).collect()

        warm_collects = warm_up(issue)
        snap1 = observe.snapshot()
        setup_s = time.perf_counter() - t_process
        _say(t_process, f"{warm_collects} warm-up collects; window opens")

        # ---- the measured window -------------------------------------
        reduced = None
        if trace:
            tdir = os.path.join(work, "trace")
            with trace_lib.capture(tdir):
                win = window_lib.run_window(
                    issue, traffic, seconds=3600.0,
                    max_queries=int(cell["trace_queries"]))
        else:
            win = window_lib.run_window(issue, traffic, seconds)
        snap2 = observe.snapshot()
        peak = memory_peak_bytes()
        _say(t_process, f"window closed after {len(win.queries)} queries")
        # ---- window closed -------------------------------------------

        if trace and keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(trace_lib.find_xplane(tdir), keep_trace)
        if trace and device["platform"] == "tpu":
            reduced = trace_lib.reduce(trace_lib.load(tdir))

        proof_lines = proofs.run_proofs(
            cfg.get("proofs", []),
            {"platform": device["platform"], "lifecycle0": lifecycle0})
        del sess

        t0 = time.perf_counter()
        answer = ref_mod.reference(tables, cfg)
        checks = check_window(win, ref_mod, answer, cfg["limits"])
        reference_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(work, ignore_errors=True)

    summary = window_lib.summarize(win)
    window_delta = observe.delta(snap1, snap2)
    setup_delta = observe.delta(snap0, snap1)
    values = {**summary, "setup_s": setup_s}
    if trace:
        obs = observe.Observation(
            queries=len(win.completed), window_s=win.end - win.start,
            window=window_delta, setup=setup_delta,
            trace=reduced, work=ref_mod.work_model(cfg, tables), peaks=peaks,
            memory_peak_bytes=peak)
        values = {m["name"]: manifest.reader(m["name"])(obs)
                  for m in per_layer}
        wanted = per_layer
    else:
        wanted = e2e
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}

    device["memory_peak_bytes"] = peak
    result = {
        "correct": is_correct(checks),
        "attempted": len(win.queries),
        "failed": len(win.queries) - len(win.completed),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
        result["device_modules"] = [
            [m, s, reduced.module_runs[m]] for m, s in
            sorted(reduced.module_s.items(), key=lambda kv: -kv[1])[:10]]
    result.update({
        "workload": workload, "seed": seed, "trace": int(trace),
        "window_s": win.end - win.start,
        "queries": summary.get("queries", 0),
        "per_query_s": [q.end - q.start for q in win.completed],
        "errors": [q.error for q in win.queries if q.error][:3],
        "window_compiles": window_delta["counters"]["traces"],
        "families": {"window": window_delta["families"],
                     "setup": setup_delta["families"]},
        "setup": {"data_s": data_s, "warm_collects": warm_collects,
                  "compile_s": setup_delta["counters"]["compile_ns"] / 1e9},
        "reference_s": reference_s,
        "proofs": proof_lines,
        "checks": checks,
    })
    return result


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on stderr."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
