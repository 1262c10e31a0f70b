#!/usr/bin/env python3
"""Result lines read the way the check reads them: for each set of runs and
each estimator of the window's per-query times, the median over the runs and
their spread; then the rule that picks `query_s`'s estimator and its bound.

    python3 benchmarks/tools/spread_readings.py SET [SET ...] [--decide]

A SET is a directory of files that each end in a result line of
`benchmarks/run.py --trace 0`, or one file of such lines. The estimators are
`lib/window.estimators` of each line's `per_query_s` and `window_s`: the
window mean, the median, the 90th percentile, the count of queries. Two
spreads, each over the set's median:

  range_drop1: the range of the runs with the one farthest from their median
               left out (the number the check holds against half the bound);
  iqr:         the distance between the quartiles of ALL the runs, as
               `statistics.quantiles(values, n=4)` gives them (the number
               the check holds a bound's looseness against).

`--decide` applies the rule of PERF.md section 2 to the sets given and prints
its steps. Needs no chip and no JAX. The last line is one JSON object."""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib.window import estimators  # noqa: E402

#: the bounds a metric may take, and how far the median may carry the rule
STEPS = (0.01, 0.015, 0.02, 0.03, 0.05, 0.08)
P90_STEPS = STEPS + (0.10,)
MEDIAN_WINS_AT = 0.6          # of the mean's widest spread
TIGHT, LOOSE = 2.0, 8.0       # bound >= 2 x widest, <= 8 x narrowest spread


def result_lines(path: str) -> list:
    """The result lines of one set: the last line of each file of a
    directory, or every line of one file that parses as a result."""
    if os.path.isdir(path):
        texts = []
        for name in sorted(os.listdir(path)):
            with open(os.path.join(path, name)) as f:
                lines = f.read().strip().splitlines()
            texts += lines[-1:]
    else:
        with open(path) as f:
            texts = f.read().strip().splitlines()
    out = []
    for t in texts:
        try:
            rec = json.loads(t)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("per_query_s") and "window_s" in rec:
            out.append(rec)
    return out


def range_drop1(values: list) -> float:
    """Range over median, the value farthest from the median left out
    (which never widens it); of fewer than three values, their range."""
    med = statistics.median(values)
    kept = sorted(values)
    if len(kept) >= 3:
        kept.remove(max(kept, key=lambda v: abs(v - med)))
    return (kept[-1] - kept[0]) / med


def iqr(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def read_set(lines: list) -> dict:
    """{estimator: {"runs", "median", "range_drop1", "iqr"}} of one set; an
    estimator that some run lacks (`p90_s` under 100 queries) is left out."""
    per_run = [estimators(r["per_query_s"], r["window_s"]) for r in lines]
    out = {}
    for name in per_run[0]:
        if not all(name in e for e in per_run):
            continue
        vals = [e[name] for e in per_run]
        out[name] = {"runs": vals, "median": statistics.median(vals),
                     "range_drop1": range_drop1(vals),
                     "iqr": iqr(vals) if len(vals) >= 2 else 0.0}
    return out


def bound_for(spreads: list, steps: tuple = STEPS):
    """(bound, fits): the smallest step that is at least twice the widest of
    `spreads`, and whether it is also within eight times the narrowest.
    Where no step reaches twice the widest: (None, False)."""
    for step in steps:
        if step >= TIGHT * max(spreads):
            return step, step <= LOOSE * min(spreads)
    return None, False


def decide(sets: dict) -> dict:
    """The rule, on every set's `range_drop1`: the median replaces the window
    mean only where its widest spread is at most 0.6 of the mean's; the
    bound comes from the chosen estimator's spreads; a median brings the
    90th percentile along as `query_p90_s` only if that gets a bound of at
    most 0.10."""
    def spreads(name):
        return [s[name]["range_drop1"] for s in sets.values() if name in s]
    mean, med, p90 = (spreads(n) for n in ("window_mean_s", "median_s", "p90_s"))
    ratio = max(med) / max(mean)
    chosen = "median_s" if ratio <= MEDIAN_WINS_AT else "window_mean_s"
    bound, fits = bound_for(spreads(chosen))
    out = {"widest": {"window_mean_s": max(mean), "median_s": max(med)},
           "narrowest": {"window_mean_s": min(mean), "median_s": min(med)},
           "median_over_mean": ratio, "query_s": chosen,
           "bound": bound if bound is not None else STEPS[-1],
           "bound_reaches_twice_the_widest": bound is not None,
           "bound_within_8x_narrowest": fits}
    if chosen == "median_s" and p90 and len(p90) == len(med):
        p_bound, p_fits = bound_for(p90, P90_STEPS)
        out["query_p90_s"] = {"widest": max(p90), "narrowest": min(p90),
                              "bound": p_bound, "within_8x_narrowest": p_fits,
                              "added": p_bound is not None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sets", nargs="+", metavar="SET")
    ap.add_argument("--decide", action="store_true")
    args = ap.parse_args(argv)

    sets = {}
    for path in args.sets:
        lines = result_lines(path)
        if not lines:
            print(f"spread_readings: no result line in {path}", file=sys.stderr)
            return 3
        bad = [r.get("seed") for r in lines
               if not r.get("correct") or r.get("failed")]
        if bad:
            print(f"spread_readings: {path}: runs not correct, seeds {bad}",
                  file=sys.stderr)
        sets[os.path.basename(os.path.normpath(path))] = read_set(lines)

    print(f"{'set':<14}{'estimator':<15}{'runs':>5}{'median':>12}"
          f"{'range_drop1':>13}{'iqr':>9}")
    for tag, est in sets.items():
        for name, r in est.items():
            print(f"{tag:<14}{name:<15}{len(r['runs']):>5}{r['median']:>12.6g}"
                  f"{100 * r['range_drop1']:>12.3f}%{100 * r['iqr']:>8.3f}%")
    summary = {"sets": sets}
    if len(sets) >= 2:
        first, *rest = sets.values()
        apart = {name: max(abs(s[name]["median"] / r["median"] - 1) for s in rest)
                 for name, r in first.items() if all(name in s for s in rest)}
        for name, a in apart.items():
            print(f"medians apart, against the first set: {name} {100 * a:.3f}%")
        summary["medians_apart"] = apart
    if args.decide:
        summary["decision"] = d = decide(sets)
        print(f"median's widest {100 * d['widest']['median_s']:.3f}% over the "
              f"mean's {100 * d['widest']['window_mean_s']:.3f}% = "
              f"{d['median_over_mean']:.3f} (the median wins at <= "
              f"{MEDIAN_WINS_AT}): query_s is {d['query_s']}")
        print(f"bound {d['bound']}: twice the widest reached: "
              f"{d['bound_reaches_twice_the_widest']}; within 8 x the "
              f"narrowest: {d['bound_within_8x_narrowest']}")
        if "query_p90_s" in d:
            print(f"query_p90_s: {d['query_p90_s']}")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
