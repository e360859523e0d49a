"""Share of the window that the loop thread spent inside LATE reads of a
decode step's tokens (``ServingMetrics``: the buckets of the plain decode
reads, ``harness/read_account.py``; late is from twice the upper edge of the
median read's bucket): the stalls in which the device had the tokens and the
host was not handed them. 0 in most runs; a run that reads low on
``serve_tokens_per_s`` with nothing here was slow for another reason."""

from benchmarks.harness import read_account


def read(run):
    rows = read_account.buckets(run.counters)
    window = run.host.get("window_s")
    if rows is None or not window:
        return None
    _, (_, late_s) = read_account.split_late(rows)
    return 100.0 * late_s / window
