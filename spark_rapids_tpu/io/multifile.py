"""Multi-file reader base — the reference's three-reader framework
(GpuMultiFileReader.scala: PERFILE, MULTITHREADED :345, COALESCING :830).

The MULTITHREADED pattern is the default here: a thread pool decodes the
next chunks on host while the device pipeline consumes the current batch,
hiding IO/decode latency exactly like the reference hides S3 fetch+footer
parse. COALESCING falls out of the chunk iterator: small files/row groups
feed the downstream CoalesceBatchesExec instead of a bespoke stitcher.
"""

from __future__ import annotations

import glob
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, List, Optional, Sequence

from ..columnar.batch import ColumnarBatch
from ..config import (MULTITHREADED_READ_FETCH_AHEAD,
                      MULTITHREADED_READ_NUM_THREADS, RapidsConf,
                      active_conf)


def expand_paths(path) -> List[str]:
    """file | directory | glob | list of any of those -> ordered file list."""
    if isinstance(path, (list, tuple)):
        out: List[str] = []
        for p in path:
            out.extend(expand_paths(p))
        return out
    path = os.fspath(path)
    if os.path.isdir(path):
        return sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if not f.startswith((".", "_")))
    if any(ch in path for ch in "*?["):
        return sorted(glob.glob(path))
    return [path]


#: ONE process-wide decode pool shared by every scan (ISSUE 3
#: satellite): per-call pools multiplied thread counts once pipeline
#: producer threads drove several scans at once, and paid pool
#: setup/teardown per batches() drive. Sized by
#: spark.rapids.sql.multiThreadedRead.numThreads; grows (never shrinks)
#: if a later conf asks for more.
_pool: Optional[ThreadPoolExecutor] = None
_pool_size = 0
_pool_lock = threading.Lock()
#: replaced-on-growth pools, kept alive for their in-flight drives
_retired: list = []


def shared_read_pool(num_threads: Optional[int] = None
                     ) -> ThreadPoolExecutor:
    """The process-wide multi-file decode pool (lazily created)."""
    global _pool, _pool_size
    if num_threads is None:
        num_threads = active_conf().get(MULTITHREADED_READ_NUM_THREADS)
    num_threads = max(1, int(num_threads))
    with _pool_lock:
        if _pool is None or num_threads > _pool_size:
            # grow-only, and the old pool is RETIRED, never shut down:
            # an in-flight threaded_chunks drive still submits to its
            # captured pool reference — shutdown() would raise
            # RuntimeError mid-scan. Growth is a rare conf event; a
            # retired pool's idle workers are an accepted cost.
            if _pool is not None:
                _retired.append(_pool)
            _pool = ThreadPoolExecutor(
                max_workers=num_threads,
                thread_name_prefix="multifile-read")
            _pool_size = num_threads
        return _pool


def fetch_ahead_window(num_threads: int,
                       conf: Optional[RapidsConf] = None) -> int:
    """Decode tasks a reader keeps in flight ahead of its consumer
    (spark.rapids.sql.multiThreadedRead.fetchAheadWindow; 0 = the
    classic 2 x numThreads)."""
    conf = conf if conf is not None else active_conf()
    window = conf.get(MULTITHREADED_READ_FETCH_AHEAD)
    return window if window > 0 else 2 * max(1, num_threads)


def threaded_chunks(tasks: Sequence[Callable[[], "object"]],
                    num_threads: int,
                    window: Optional[int] = None) -> Iterator["object"]:
    """Decode `tasks` with a bounded look-ahead window on the shared
    pool, yielding in order (the multithreaded cloud reader: fetch
    ahead, emit in sequence). Every decode task runs under bounded IO
    retry (io/retrying.py): a transient OSError — a flaky mount, an
    object-store hiccup, an injected `io.multifile_read` fault — backs
    off and re-reads instead of killing the scan."""
    from .retrying import with_io_retry
    from ..exec import lifecycle
    from ..obs import events as obs_events
    from ..obs import op_span
    conf = active_conf()  # captured HERE: pool threads see default conf
    # the query id too (ISSUE 12): the shared pool serves every query,
    # so io_retry events from a decode task must carry the SUBMITTING
    # thread's attribution, not the pool thread's empty TLS
    qid = obs_events.current_query_id()
    # and the lifecycle context, per job like the query id: it carries
    # the query's phase ledger, so a pool thread's scan-decode time
    # reaches the query's books (folded) and not only the global ones
    lctx = lifecycle.current_context()

    def decode(t: Callable[[], "object"], i: int) -> "object":
        prev = lifecycle.current_context()
        lifecycle.adopt_context(lctx)
        try:
            # the span covers every attempt: a retry's backoff and
            # re-read are time this chunk's decode took
            with op_span("scan.decode", phase="scan-decode"):
                # per-chunk jitter salt: concurrent decode tasks on one
                # flaky mount must not back off in lockstep
                return with_io_retry(
                    t, "multifile_read", conf=conf,
                    fault_point="io.multifile_read", salt=str(i))
        finally:
            lifecycle.adopt_context(prev)

    def retrying(t: Callable[[], "object"], i: int) -> "object":
        return obs_events.with_query_id(qid, decode, t, i)

    if num_threads <= 1 or len(tasks) <= 1:
        for i, t in enumerate(tasks):
            yield retrying(t, i)
        return
    pool = shared_read_pool(max(
        num_threads, conf.get(MULTITHREADED_READ_NUM_THREADS)))
    if window is None:
        window = fetch_ahead_window(num_threads)
    futures = [pool.submit(retrying, t, i)
               for i, t in enumerate(tasks[:window])]
    next_submit = window
    try:
        for i in range(len(tasks)):
            yield futures[i].result()
            futures[i] = None  # release
            if next_submit < len(tasks):
                futures.append(pool.submit(retrying, tasks[next_submit],
                                           next_submit))
                next_submit += 1
    finally:
        # abandoned mid-drive (limit/short-circuit): cancel what never
        # started so the shared pool isn't left decoding dead work
        for f in futures:
            if f is not None:
                f.cancel()


def arrow_to_batches(table, target_rows: int) -> Iterator[ColumnarBatch]:
    """Split a host arrow table into device batches of ~target_rows.
    The slice offset keys each batch's upload for seeded chaos (the
    work item is the row range, not the thread that happens to decode
    it)."""
    n = table.num_rows
    if n == 0:
        yield ColumnarBatch.from_arrow(table, fault_key="scan:0")
        return
    for start in range(0, n, target_rows):
        yield ColumnarBatch.from_arrow(table.slice(start, target_rows),
                                       fault_key=f"scan:{start}")
