"""Share of the positions the prefill programs computed that no prompt
needed (``ServingMetrics``: 1 - ``prefill_tokens`` over
``prefill_positions_run``): padding up to the bucket and the empty rows of
a batch that runs at the pool's width whatever the group's size."""


def read(run):
    ran = run.counters.get("prefill_positions_run", 0)
    if not ran:
        return None
    return 100.0 * (1.0 - run.counters.get("prefill_tokens", 0) / ran)
