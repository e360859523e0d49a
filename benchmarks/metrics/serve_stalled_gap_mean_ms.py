"""Mean length of the token gaps in which the loop ran a prefill
(``ServingMetrics``: ``stalled_gap_s`` over ``stalled_gaps``)."""


def read(run):
    stalled = run.counters.get("stalled_gaps", 0)
    if not stalled:
        return None
    return 1e3 * run.counters.get("stalled_gap_s", 0) / stalled
