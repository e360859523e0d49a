"""Host time behind each decode step (``ServingMetrics``: ``decode_host_s``,
from the token read-back's return to the end of the loop iteration, over
``decode_steps``): emit, stream callbacks, retire, bookkeeping."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps or "decode_host_s" not in run.counters:
        return None
    return 1e3 * run.counters["decode_host_s"] / steps
