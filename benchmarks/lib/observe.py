"""What the benchmark reads from the program: the dispatch ledger, the phase
ledger and the lifecycle counters, as snapshots and their differences."""

#: ledger labels of the scan -> upload -> coalesce path; every other label
#: is a stage program (step, sizing, probe, filter, sort)
INGEST_PREFIXES = ("upload.", "coalesce.")


def is_ingest(label: str) -> bool:
    return label.startswith(INGEST_PREFIXES)


def ledger_by_label() -> dict:
    """{label: {"dispatches", "traces", "compile_ns"}} summed over the
    label's shape buckets."""
    from spark_rapids_tpu.obs import dispatch
    out = {}
    for p in dispatch.programs():
        rec = out.setdefault(p["label"],
                             {"dispatches": 0, "traces": 0, "compile_ns": 0})
        for k in rec:
            rec[k] += p[k]
    return out


def snapshot() -> dict:
    from spark_rapids_tpu.obs import dispatch, phase
    return {"counters": dispatch.counters(), "labels": ledger_by_label(),
            "phases": phase.counters()}


def delta(a: dict, b: dict) -> dict:
    """b - a of two snapshots, label by label."""
    labels = {}
    for label, rec in b["labels"].items():
        old = a["labels"].get(label, {})
        d = {k: v - old.get(k, 0) for k, v in rec.items()}
        if any(d.values()):
            labels[label] = d
    return {
        "counters": {k: v - a["counters"].get(k, 0)
                     for k, v in b["counters"].items()},
        "labels": labels,
        "phases": {k: v - a["phases"].get(k, 0)
                   for k, v in b["phases"].items()},
    }


class Observation:
    """What a per-layer metric's reader is given. A reader that finds
    nothing to read returns None and the metric is left out of the line."""

    def __init__(self, *, queries, window_s, window, setup, trace, work,
                 peaks, memory_peak_bytes):
        self.queries = queries        # queries completed in the traced window
        self.window_s = window_s      # its length on the host's clock
        self.window = window          # ledger/phase delta over it
        self.setup = setup            # ledger/phase delta over set-up
        self.trace = trace            # lib.trace.Reduced, or None
        self.work = work              # the configuration's work model
        self.peaks = peaks            # this device kind's peaks
        self.memory_peak_bytes = memory_peak_bytes  # fullest chip, after the window

    def dispatches(self, keep) -> int:
        """Window dispatches of the labels for which `keep(label)`."""
        return sum(rec["dispatches"]
                   for label, rec in self.window["labels"].items()
                   if keep(label))
