"""Share of the gaps between consecutive tokens of one request in which
the loop ran a prefill (``ServingMetrics``: ``stalled_gaps`` over
``token_gaps``; stalled by cause, not by a threshold)."""


def read(run):
    gaps = run.counters.get("token_gaps", 0)
    if not gaps:
        return None
    return 100.0 * run.counters.get("stalled_gaps", 0) / gaps
