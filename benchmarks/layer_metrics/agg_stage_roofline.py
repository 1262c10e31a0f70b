"""kernels: the fused filter + project + masked-bucket aggregate stage's
share of its roofline (memory-bound: bytes over the HBM peak).

Name-to-label table: a program is found in the trace by the XLA module name
its jit gives it. `InstrumentedJit` wraps the stage's function with
`functools.wraps`, so the module is `jit_<function name>`: the stage's
`_agg_spec_body` shows as `jit__agg_spec_body` (seen in a v5e trace, PR 26)."""

from benchmarks.lib.roofline import roofline_share

MODULES = {"jit__agg_spec_body": "CompiledStageExec.step"}


def read(obs):
    return roofline_share(obs, "agg_stage", MODULES)
