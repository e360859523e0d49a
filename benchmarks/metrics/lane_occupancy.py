"""Mean share of the decode lanes that held a request, over the decode
steps of the window (``ServingMetrics``: tokens emitted per decode step over
``max_slots``)."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps:
        return None
    return (100.0 * run.counters.get("tokens_emitted", 0) / steps
            / run.host["max_slots"])
