"""scan + upload + coalesce: share of the scanned columns that the host pack
wrote straight from their Arrow buffers into the staging buffer (PR 34:
fixed-width types) instead of building a host `Column` first (strings,
dictionaries, DECIMAL, BOOLEAN, nested): `100 * direct_cols / (direct_cols +
built_cols)` of the window's delta of `columnar/upload.counters()`. A built
column costs the serial scan producer passes and temporaries a batch
(`upload_ms`). 0 is a reading: every column was built. None where nothing
was packed in the window, or the program has no such counters."""

from benchmarks.lib.observe import family


def read(obs):
    c = family(obs, "upload")
    if not {"direct_cols", "built_cols"} <= set(c):
        return None
    packed = c["direct_cols"] + c["built_cols"]
    if not packed:
        return None
    return 100.0 * c["direct_cols"] / packed
