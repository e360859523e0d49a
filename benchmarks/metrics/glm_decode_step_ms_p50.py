"""Median device duration of one execution of the GLM-5.2 decode program
(``jit__glm_decode_step_jit`` in the trace)."""

from benchmarks.harness import stats

PROGRAM = "jit__glm_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    return 1e3 * stats.percentile(xs, 50) if xs else None
