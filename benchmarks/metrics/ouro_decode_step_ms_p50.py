"""Median device duration of one execution of the Ouro decode program
(``jit__ouro_decode_step_jit`` in the trace): every pass of the stack for
every lane."""

from benchmarks.harness import stats

PROGRAM = "jit__ouro_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    return 1e3 * stats.percentile(xs, 50) if xs else None
