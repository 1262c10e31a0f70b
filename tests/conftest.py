"""Test fixture: run the engine on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (the reference's analog is
the NUM_LOCAL_EXECS pseudo-cluster, run_pyspark_from_build.sh:138).

The installed JAX honours `JAX_PLATFORMS=cpu`, and the tier-1 command sets
it. The in-code pin below stays for the run that forgets the variable: on a
machine with a chip every xdist worker would otherwise try to take the TPU,
and a chip belongs to one process at a time.
CPU also gives correctly-rounded f64, the reference oracle for Spark
semantics; TPU f64 is double-float emulated (documented divergence, like the
reference's docs/compatibility.md floating-point section).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# The package places a persistent compile cache (spark_rapids_tpu/__init__).
# The CPU suite does without it: six workers would share one directory, and
# reloading XLA:CPU entries only adds machine-feature noise to the log.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_fault_plan():
    """A chaos plan (spark.rapids.tpu.test.faults) leaked by one module
    would silently inject faults into every later suite — disarm at
    module boundaries and fail the offender loudly (ISSUE 4)."""
    from spark_rapids_tpu import faults
    faults.install(None)
    yield
    leaked = faults.active_plan()
    faults.install(None)
    assert leaked is None, (
        f"module leaked an armed fault plan: {leaked.spec_string!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_workload_state():
    """Workload-governor hygiene (ISSUE 7, mirroring the lifecycle
    tripwire): a query left queued or admitted at a module boundary
    means some admitted() scope never released its ticket — later
    suites would inherit a phantom tenant whose quota share shrinks
    everyone else's. Reset at module boundaries and fail the offender
    loudly."""
    from spark_rapids_tpu.exec import workload
    workload.reset_workload()
    yield
    snap = workload.snapshot()
    workload.reset_workload()
    assert snap["queue_depth"] == 0 and snap["admitted"] == 0, (
        f"module leaked workload state: {snap['queue_depth']} queued, "
        f"{snap['admitted']} admitted")


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_staging_buffers():
    """Packed-upload staging-pool hygiene (ISSUE 10, mirroring the
    lifecycle/workload tripwires): an upload that fails to release its
    staging buffer leaks host memory forever (the pool can only reuse
    what comes back) — assert in-flight bytes return to the zero
    baseline at module boundaries and fail the offender loudly. Idle
    (pooled) buffers are the pool working as designed and may persist."""
    from spark_rapids_tpu.columnar import upload
    yield
    pool = upload.staging_pool()
    pool.settle()  # flush deferred (release-when-ready) buffers
    leaked = pool.outstanding_bytes()
    if leaked:
        upload.reset_staging_pool()
    assert leaked == 0, (
        f"module leaked {leaked} bytes of in-flight upload staging "
        f"buffers (acquire without release/discard)")


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_telemetry_state():
    """Telemetry-plane hygiene (ISSUE 11, mirroring the lifecycle/
    workload tripwires): a registry left enabled keeps a
    `telemetry-*` sampler thread alive into every later suite — reset
    at module boundaries and fail the offender loudly if its exporter
    thread survives the reset."""
    import threading

    from spark_rapids_tpu.obs import stats as runtime_stats
    from spark_rapids_tpu.obs import telemetry
    telemetry.reset_telemetry()
    runtime_stats.reset_stats()
    yield
    telemetry.reset_telemetry()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("telemetry-") and t.is_alive()]
    assert not leaked, (
        f"module leaked telemetry exporter thread(s): {leaked}")


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_history_state():
    """Phase/history-plane hygiene (ISSUE 17, the telemetry pattern): a
    module that enabled the query-history store must not leave later
    suites appending capsules to its tmpdir (the file handle would
    outlive the tmpdir fixture), and the process-global phase counters
    must not bleed across modules' bench-delta assertions — reset both
    at module boundaries."""
    from spark_rapids_tpu.obs import history
    from spark_rapids_tpu.obs import phase
    history.reset_history()
    phase.reset_phase_counters()
    yield
    history.reset_history()
    phase.reset_phase_counters()


@pytest.fixture(scope="module", autouse=True)
def _dispatch_ledger_reset():
    """Dispatch-plane hygiene (ISSUE 13): a module that disabled the
    ledger (dispatch.ledger.enabled=false session) must not leave the
    default-on plane dark for every later suite, and a module's
    program records must not bleed into another's dispatch_summary
    assertions — reset to a fresh default-enabled ledger at module
    boundaries."""
    from spark_rapids_tpu.obs import dispatch
    dispatch.reset_dispatch_ledger()
    yield
    dispatch.reset_dispatch_ledger()


@pytest.fixture(scope="module", autouse=True)
def _stage_compiler_reset():
    """Whole-stage-compilation hygiene (ISSUE 14): the plan-fingerprint
    program-site cache (cleared with the ledger above, but only at
    reset points) and the stage counters/size caches are process-wide —
    a module asserting fresh-trace behavior or per-lane stage deltas
    must not inherit another module's warm caches."""
    from spark_rapids_tpu.exec import stage_compiler
    stage_compiler.reset_stage_counters()
    yield
    stage_compiler.reset_stage_counters()


@pytest.fixture(scope="module", autouse=True)
def _adaptive_counters_reset():
    """Adaptive-replanner hygiene (ISSUE 19, the dispatch pattern): the
    decision counters are process-wide and several suites assert exact
    deltas (skew splits taken, demotions observed) — zero them at
    module boundaries so one module's replans don't bleed into
    another's assertions."""
    from spark_rapids_tpu.exec import adaptive
    adaptive.reset_adaptive()
    yield
    adaptive.reset_adaptive()


@pytest.fixture(scope="module", autouse=True)
def _speculation_shield_reset():
    """Straggler-shield hygiene (ISSUE 20, the adaptive pattern): the
    shield counters (stalls, spec wins/denials, dispatch timeouts,
    peer invalidations) are process-wide and asserted as deltas, and a
    heartbeat manager left installed would keep routing peer_dead
    transitions into later suites — zero both at module boundaries."""
    from spark_rapids_tpu.exec import speculation_shield
    from spark_rapids_tpu.parallel import heartbeat
    speculation_shield.reset_shield()
    heartbeat.install(None)
    yield
    speculation_shield.reset_shield()
    heartbeat.install(None)


@pytest.fixture(scope="module", autouse=True)
def _no_leaked_lifecycle_state():
    """Lifecycle-governor hygiene (ISSUE 6, same pattern as the leaked
    fault plan): a breaker left open would silently demote a kernel
    tier for every later suite, and a QueryContext left registered
    means some query never unwound its governed scope — reset at module
    boundaries and fail the offender loudly."""
    from spark_rapids_tpu.exec import lifecycle
    lifecycle.reset_lifecycle()
    yield
    leaked_queries = lifecycle.active_query_ids()
    leaked_breakers = lifecycle.open_breakers()
    lifecycle.reset_lifecycle()
    assert not leaked_queries, (
        f"module leaked registered query contexts: {leaked_queries}")
    assert not leaked_breakers, (
        f"module left circuit breakers open: {leaked_breakers}")
