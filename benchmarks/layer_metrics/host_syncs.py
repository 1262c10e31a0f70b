"""device: device-to-host reads per query, the WINDOW's delta of `syncs` in
`obs.dispatch.counters()`: every read through the one door
`obs.dispatch.host_read` (span `sync.<site>`; `dispatch.sync_sites()` has
them by site). 0 is a reading: no query read the device before its result.
None where the program has no such counter (before PR 40), or no query
completed."""


def read(obs):
    c = obs.window["counters"]
    if "syncs" not in c or not obs.queries:
        return None
    return c["syncs"] / obs.queries
