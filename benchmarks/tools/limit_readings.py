#!/usr/bin/env python3
"""The two readings a limit is set from, on the chip at the cell's own size,
many seeds in ONE process (one compile):

  lower: the largest value of each compared number that sound runs of the
         program give (a short window per seed at the cell's own load);
  upper: what the control gives: the plain reference computed in float32
         (the configuration states float64) and put in the program's place.

    python3 benchmarks/tools/limit_readings.py --workload q6_scan_filter_sum \
        --seeds 12 --first-seed 2000000011 --queries 2 [--out file.jsonl]

Not part of a benchmark run. Prints one JSON line per seed and a summary."""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2000000011)
    ap.add_argument("--queries", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.run import place_compile_cache
    place_compile_cache()
    import numpy as np
    from benchmarks.lib import datagen, harness, window
    from benchmarks.lib.manifest import Manifest, apply_rehearsal

    m = Manifest()
    cell = m.cell(args.workload)
    cfg = m.config(cell["config"])
    if args.rehearse:
        cfg = apply_rehearsal(cfg)
    traffic = m.traffic(cell["traffic"])
    ref, query = m.config_module(cfg, "reference"), m.config_module(cfg, "query")
    device = harness.device_record(not args.rehearse, int(cell["chips"]))

    from spark_rapids_tpu.api.session import TpuSession
    sess = TpuSession(dict(cfg.get("session_conf", {})))
    work = os.path.join(ROOT, ".bench_work", "limit_readings")
    lines = []
    try:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            shutil.rmtree(work, ignore_errors=True)
            tables = ref.generate(seed, cfg)
            paths = datagen.write_tables(os.path.join(work, str(seed)),
                                         tables, cfg["schema"], cfg["layout"])

            def issue():
                return query.build(sess, paths, cfg).collect()

            if i == 0:
                harness.warm_up(issue)
            win = window.run_window(issue, traffic, 3600.0,
                                    max_queries=args.queries)
            answer = ref.reference(tables, cfg)
            program = harness.check_window(win, ref, answer, cfg["limits"])
            control = ref.compare(
                ref.as_rows(ref.reference(tables, cfg, np.float32)), answer)
            rec = {"workload": args.workload, "seed": seed, "device": device,
                   "queries": len(win.completed),
                   "program": {k: v["value"] for k, v in program.items()},
                   "control_float32": control}
            lines.append(rec)
            print(json.dumps(rec), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    names = list(cfg["limits"])
    summary = {"workload": args.workload, "seeds": len(lines),
               "lower": {k: max(r["program"][k] for r in lines) for k in names},
               "upper_control": {k: min(r["control_float32"][k] for r in lines)
                                 for k in names},
               "limits": cfg["limits"]}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            for r in lines + [summary]:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
