"""fused stages: share of the hash group-by's updates and merges that took
the lane tier (PR 36: short string keys and fixed-width keys as packed lanes
through the masked-bucket kernel, one program and one host read a batch)
instead of the hash rounds: `100 * lane_updates / hash_updates` of the
window's delta of `exec/aggregate.counters()`. 100 where every key fits 16
bytes and the groups resolve; a fall to the hash rounds costs Q1 20 s a
query. 0 is a reading: hash updates ran and none rode lanes. None where no
hash update ran in the window, or the program has no such counters."""

from benchmarks.lib.observe import family


def read(obs):
    c = family(obs, "aggregate")
    if not {"lane_updates", "hash_updates"} <= set(c) or not c["hash_updates"]:
        return None
    return 100.0 * c["lane_updates"] / c["hash_updates"]
