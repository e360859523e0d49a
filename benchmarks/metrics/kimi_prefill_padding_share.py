"""Share of the positions the chunked prefill program computed that no
prompt needed (``ServingMetrics``: 1 - ``prefill_tokens`` over
``prefill_positions_run``): the tail of a prompt's last chunk and the empty
rows of a call. Read only where the program counts chunks
(``prefill_chunks``), which GPT-2's bucketed prefill does not."""


def read(run):
    ran = run.counters.get("prefill_positions_run", 0)
    if not ran or not run.counters.get("prefill_chunks", 0):
        return None
    return 100.0 * (1.0 - run.counters.get("prefill_tokens", 0) / ran)
