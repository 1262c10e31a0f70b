"""spark_rapids_tpu — a TPU-native columnar SQL execution engine.

A from-scratch rebuild of the capabilities of the RAPIDS Accelerator for
Apache Spark (reference: binmahone/spark-rapids), designed TPU-first:

  * compute path: JAX/XLA programs + Pallas kernels over device-resident
    Arrow-like columns (static capacity buckets, device row counts);
  * scale-out: jax.sharding Mesh + shard_map with ICI collectives replacing
    the reference's UCX/NVLink shuffle transport;
  * memory: HBM budget manager with host/disk spill tiers and a
    retry/split-retry discipline mirroring the reference's RMM-based
    RmmRapidsRetryIterator contract;
  * planning: declarative override rule tables (wrap -> tag -> convert)
    mirroring GpuOverrides/RapidsMeta, operating on this engine's logical
    plans.

Spark-semantics fidelity (LongType/DoubleType/Decimal/hash parity) requires
64-bit lanes, so x64 mode is enabled at import — TPUs emulate i64/f64; hot
kernels deliberately stay in 32-bit lanes where Spark semantics allow.
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

#: Persistent compile cache. `JAX_COMPILATION_CACHE_DIR` places it from
#: outside (jax reads the variable itself; nothing is set here). Unset,
#: it is ONE fixed directory in the checkout: the path is part of the
#: cache key, so never a temp dir, a pid or the time.
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir",
                       DEFAULT_COMPILE_CACHE_DIR)

from . import types  # noqa: E402
from .columnar.column import Column, StringColumn, bucket_capacity  # noqa: E402
from .columnar.batch import ColumnarBatch  # noqa: E402
# the error taxonomy is public API: callers catching engine failures
# distinguish the OOM lane (memory.retry.TpuOOMError) from transient
# task-lane failures and integrity quarantines (docs/robustness.md)
from .faults import IntegrityError, TpuTaskRetryError  # noqa: E402
# a deadline-expired or user-cancelled governed query unwinds with this
# (exec/lifecycle.py; TpuSession.cancel_query / query.timeoutMs)
from .exec.lifecycle import QueryCancelledError  # noqa: E402
# the workload governor refused to start the query (queue full /
# admission timeout / known-degraded device) — carries reason and a
# retry_after_ms hint (exec/workload.py; spark.rapids.tpu.workload.*)
from .exec.workload import QueryAdmissionError  # noqa: E402
from .version import __version__  # noqa: E402
