"""Share of the traced window the device spent in the prefill programs:
``jit__prefill_batch_jit`` and the lane install that follows it."""

PROGRAMS = ("jit__prefill_batch_jit", "jit__install_pages")


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.program_time(PROGRAMS) / t.window_s
