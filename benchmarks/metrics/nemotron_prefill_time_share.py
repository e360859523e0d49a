"""Share of the traced window the device spent in the Nemotron-H prefill
program (``jit__nemotron_prefill_chunk_jit``) and the slot reset that
precedes a prompt's first row (``jit__zero_slot``)."""

PROGRAMS = ("jit__nemotron_prefill_chunk_jit", "jit__zero_slot")


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    if not t.program_durations(PROGRAMS[0]):
        return None
    return 100.0 * t.program_time(PROGRAMS) / t.window_s
