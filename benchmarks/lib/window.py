"""The one general load generator, and the arithmetic of the window.

A traffic mix is a data file: `loop` (closed) and `clients` (1: a mix with
more clients brings the code that drives them, with the cell that needs it).
The client issues its next query as soon as its last returned, starts
queries until `seconds` have passed and lets the one in flight finish.
Nothing is quantised: the window ends when the last query ends. `query_s` is
the window's length over the queries it completed (the window mean).
"""

import statistics
import time
from dataclasses import dataclass, field

#: a 90th percentile is read only from a window with ten samples beyond it
P90_MIN_QUERIES = 100


@dataclass
class Query:
    start: float
    end: float
    result: object = None      # rows, or None where it failed
    error: str = ""


@dataclass
class Window:
    start: float
    end: float
    queries: list = field(default_factory=list)

    @property
    def completed(self) -> list:
        return [q for q in self.queries if not q.error]


def run_window(issue, traffic: dict, seconds: float, max_queries=None,
               clock=time.perf_counter) -> Window:
    """Drive `issue()` as the traffic mix says for `seconds`. With
    `max_queries` the client stops after that many (the traced window).
    A failed query is counted, not fatal; three in a row stop the client:
    the program is broken."""
    if traffic.get("loop") != "closed" or int(traffic.get("clients", 1)) != 1:
        raise ValueError(f"traffic {traffic.get('name')!r}: the generator "
                         "offers one closed-loop client")
    start = clock()
    deadline = start + seconds
    queries = []
    fails = 0
    while clock() < deadline and fails < 3 and (
            max_queries is None or len(queries) < max_queries):
        t0 = clock()
        try:
            q = Query(t0, 0.0, result=issue())
            fails = 0
        except Exception as e:  # boundary: see the docstring
            q = Query(t0, 0.0, error=f"{type(e).__name__}: {e}")
            fails += 1
        q.end = clock()
        queries.append(q)
    end = queries[-1].end if queries else clock()
    return Window(start, end, queries)


def estimators(per_query_s: list, window_s: float) -> dict:
    """One window's per-query times read every way the benchmark reads them
    (`tools/spread_readings.py` reads recorded lines through this too):
    `queries`, the sample count; `window_mean_s`, the whole window over its
    queries, so a stall anywhere in it shows; `median_s`; and `p90_s`
    (`statistics.quantiles`, n=10), only where ten samples lie beyond it."""
    out = {"queries": len(per_query_s),
           "window_mean_s": window_s / len(per_query_s),
           "median_s": statistics.median(per_query_s)}
    if len(per_query_s) >= P90_MIN_QUERIES:
        out["p90_s"] = statistics.quantiles(per_query_s, n=10)[-1]
    return out


def summarize(w: Window) -> dict:
    """`query_s` and `queries`, its sample count. `query_s` is the window
    mean: PR 30 read the cell's runs every way `estimators` offers and the
    median spread no less from run to run (PERF.md section 2)."""
    done = w.completed
    if not done:
        return {}
    e = estimators([q.end - q.start for q in done], w.end - w.start)
    return {"query_s": e["window_mean_s"], "queries": e["queries"]}
