"""Share of the traced window the device spent in the Ouro prefill program
(``jit__ouro_prefill_chunk_jit``; admission runs no program of its own: no
state is a slot's)."""

PROGRAM = "jit__ouro_prefill_chunk_jit"


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    if not t.program_durations(PROGRAM):
        return None
    return 100.0 * t.program_time((PROGRAM,)) / t.window_s
