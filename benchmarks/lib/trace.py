"""From a profiler trace to device numbers. Nothing but JAX reads the file
(`jax.profiler.ProfileData`); the arithmetic works on plain `Event`s so that
it can be tested on a recorded trace and on hand-made ones.

How a v5e trace is laid out (looked at by hand, PR 26): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` holds one event per executed HLO op
and whose line `XLA Modules` holds one event per executed program, named
`<module>(<fingerprint>)`, e.g. `jit_step(123...)`. The host's threads are
lines of the plane `/host:CPU`; `jax.profiler.TraceAnnotation`s are events
there under their own names. All on one clock, in nanoseconds.
"""

import contextlib
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.window"
NAMED_GAPS = 1000     # a traced window of 8 q6 queries leaves ~900 gaps (PR 30)
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@contextlib.contextmanager
def capture(log_dir: str):
    """A profiler trace of the body: device events and the host's
    TraceAnnotations, without the Python tracer (it slows the host and
    swells the file)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> list:
    """The events the reduction reads: the chips' op and module lines and
    every host event with a duration."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if e.duration_ns > 0:
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals: list) -> list:
    """Sorted, merged [start, end] pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events, lo, hi):
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def module_name(event_name: str) -> str:
    """`jit_step(1234)` -> `jit_step`."""
    return _FINGERPRINT.sub("", event_name)


@dataclass
class Reduced:
    chips: int
    window_s: float          # the traced window on the trace's clock
    busy_s: float            # union of device-op intervals, mean over chips
    module_s: dict           # {module: seconds of its executions}, mean over chips
    module_runs: dict        # {module: executions}, summed over chips
    device_ops: list         # [[op, seconds]] the ten that took most time
    idle_gaps: list          # [[what the host was doing, seconds]] ten longest
    idle_s: float = field(init=False)

    def __post_init__(self):
        self.idle_s = self.window_s - self.busy_s


def _name_gap(gap, host_events) -> str:
    """The innermost host event that covers the middle of the gap."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for e in host_events:
        if e.start_ns <= mid <= e.end_ns and \
                (best is None or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "unattributed"


def reduce(events: list) -> Reduced:
    """Busy time is the union of the `XLA Ops` intervals of a chip inside the
    window the benchmark marked (`bench.window`; absent, the span of the
    device events). A program's time is the sum of its `XLA Modules` events.
    Idle gaps are the complement of busy on the first chip, the
    `NAMED_GAPS` longest named by the innermost host event over their
    middle, summed by name."""
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    ops = {p: [e for e in events if e.plane == p and e.line == OPS_LINE]
           for p in planes}
    if not any(ops.values()):
        raise ValueError("no operation ran on the device in the trace")
    host = [e for e in events if e.plane.startswith(HOST_PLANE_PREFIX)]
    marks = [e for e in host if e.name == WINDOW_MARK]
    if marks:
        lo, hi = marks[0].start_ns, marks[0].end_ns
    else:
        dev = [e for p in planes for e in ops[p]]
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    host = [e for e in host if not e.name.startswith("bench.")]

    busy = {p: union(_clip(ops[p], lo, hi)) for p in planes}
    busy_s = sum(sum(e - s for s, e in busy[p]) for p in planes) \
        / len(planes) / 1e9

    module_s, module_runs = {}, {}
    for e in events:
        if e.line == MODULES_LINE and e.plane in ops and \
                e.end_ns > lo and e.start_ns < hi:
            m = module_name(e.name)
            module_s[m] = module_s.get(m, 0.0) + e.dur_ns / 1e9 / len(planes)
            module_runs[m] = module_runs.get(m, 0) + 1

    op_s = {}
    for p in planes:
        for e in ops[p]:
            if e.end_ns > lo and e.start_ns < hi:
                op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns / 1e9
    device_ops = [[k[:160], v] for k, v in
                  sorted(op_s.items(), key=lambda kv: -kv[1])[:10]]

    first = busy[planes[0]]
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    gap_s = {}
    for g in gaps[:NAMED_GAPS]:
        name = _name_gap(g, host)
        gap_s[name] = gap_s.get(name, 0.0) + (g[1] - g[0]) / 1e9
    rest = sum(g[1] - g[0] for g in gaps[NAMED_GAPS:]) / 1e9
    if rest:
        gap_s["shorter_gaps"] = rest
    idle_gaps = [[k, v] for k, v in
                 sorted(gap_s.items(), key=lambda kv: -kv[1])[:10]]

    return Reduced(chips=len(planes), window_s=(hi - lo) / 1e9, busy_s=busy_s,
                   module_s=module_s, module_runs=module_runs,
                   device_ops=device_ops, idle_gaps=idle_gaps)
