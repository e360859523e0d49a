"""Mean time a request sat in the scheduler's queue before its prefill
was dispatched (``ServingMetrics``: ``queue_wait_s`` over ``queue_waits``,
from the request's submit stamp)."""


def read(run):
    waits = run.counters.get("queue_waits", 0)
    if not waits:
        return None
    return 1e3 * run.counters.get("queue_wait_s", 0) / waits
