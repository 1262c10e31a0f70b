"""fused stages: share of the lane tier's updates that ran on the row mask of
the filter/project chain their aggregate absorbed, the batch never compacted
(no sort and no row gathers of a batch Q1 keeps 98.6% of): `100 *
lane_masked_updates / lane_updates` of the window's delta of
`exec/aggregate.counters()`. 100 where every lane update of the window had
its chain inline; 0 is a reading: lane updates ran and none on a mask (the
chain became a map stage of its own, which compacts). None where no lane
update ran in the window, or the program has no such counters."""

from benchmarks.lib.observe import family


def read(obs):
    c = family(obs, "aggregate")
    if not {"lane_masked_updates", "lane_updates"} <= set(c) \
            or not c["lane_updates"]:
        return None
    return 100.0 * c["lane_masked_updates"] / c["lane_updates"]
