"""Share of the traced window the device spent in the GLM-5.2 prefill
program (``jit__glm_prefill_chunk_jit``; admission runs no program of its
own: the pool has no slot array to reset)."""

PROGRAM = "jit__glm_prefill_chunk_jit"


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    if not t.program_durations(PROGRAM):
        return None
    return 100.0 * t.program_time((PROGRAM,)) / t.window_s
