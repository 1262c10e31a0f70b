#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Loads the cell, its configuration, traffic mix and per-layer
readers by name, makes the data from the seed, warms the cell's own query,
measures, checks every answer of the window against the plain reference, and
prints one JSON object as the last line of standard output. It refuses to
run (exit 2, no result) unless JAX's first device is a TPU. `--rehearse`
drives the same code on the CPU at the configuration's `rehearse` size and
never ends in a line that could be taken for a result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def place_compile_cache() -> None:
    """Before JAX is imported: the persistent compile cache goes where
    `JAX_COMPILATION_CACHE_DIR` says, else to one fixed directory in the
    checkout (the path is part of the cache's key). Every program is kept,
    however short its compile, so that a second run compiles nothing it
    can load."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; prints no result")
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1: copy the .xplane.pb to DIR")
    args = ap.parse_args(argv)

    place_compile_cache()
    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest, ManifestError
    try:
        manifest = Manifest()
        seconds = args.seconds if args.seconds is not None \
            else float(manifest.doc["run_seconds"])
        result = harness.run_cell(
            args.workload, args.seed, seconds, bool(args.trace),
            manifest=manifest, require_tpu=not args.rehearse,
            rehearse=args.rehearse, t_process=T_PROCESS,
            keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"benchmarks/run.py: {e}. Run it through the chip tool.",
              file=sys.stderr)
        return 2
    except (ManifestError, ModuleNotFoundError) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3

    harness.print_checks(result["checks"])
    if args.rehearse:
        # counts and checks only: a time from a CPU is no device metric
        keep = ("correct", "attempted", "failed", "window_compiles",
                "proofs", "checks")
        print("rehearsal " + json.dumps({k: result[k] for k in keep}),
              file=sys.stderr)
        print("rehearsal only: not a result")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
