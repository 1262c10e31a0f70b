"""device: the whole query's share of the chip's memory roofline: the least
time the chip could take for every byte the query needs touched (the sum of
the configuration's work model), over the chip's busy time a query. It
bounds the kernels' rooflines from below: a PR that takes a program off the
path leaves that program's roofline silent, and this still reads."""

from benchmarks.lib.peaks import roofline_seconds


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0 or not obs.queries:
        return None
    least = sum(roofline_seconds(w, obs.peaks) for w in obs.work.values())
    return 100.0 * least * obs.queries / obs.trace.busy_s
