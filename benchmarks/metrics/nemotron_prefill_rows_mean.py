"""Rows of a prefill call that carried a prompt's tokens, on average
(``ServingMetrics``: ``prefill_chunk_rows`` over ``prefill_chunks``). A call
costs the same whatever its rows hold (its shape is fixed and it reads every
expert), so this is what a call's cost is shared over. Read only where the
program counts rows, which a prefill of one prompt a call does not."""


def read(run):
    calls = run.counters.get("prefill_chunks", 0)
    if not calls or "prefill_chunk_rows" not in run.counters:
        return None
    return run.counters["prefill_chunk_rows"] / calls
