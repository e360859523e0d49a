"""Mean wall time of the loop's decode call over the window
(``ServingMetrics``: ``decode_time_s`` over ``decode_steps``): the dispatch
of a step and the blocking read of the tokens of the step before it. Beside
``laguna_decode_step_ms_p50`` (the device's own step, from the trace) it
says what the host adds: a read that returns late (stalls of 80-200 ms were
found on some machines, PERF.md section 6) lengthens this and not that."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps or "decode_time_s" not in run.counters:
        return None
    return 1e3 * run.counters["decode_time_s"] / steps
