"""Mean blocking read of a decode step's tokens, the late ones and those
behind a prefill program left out (``ServingMetrics``: the buckets of the
plain decode reads, ``harness/read_account.py``). For a synchronous step
(GPT-2) it is the program's own time and what the read-back adds behind it:
less the device's median step, the after-end side of a decode call. For a
family with a step in flight it is what is left of a step once the host's
work is hidden behind it."""

from benchmarks.harness import read_account


def read(run):
    rows = read_account.buckets(run.counters)
    if rows is None:
        return None
    (reads, seconds), _ = read_account.split_late(rows)
    return 1e3 * seconds / reads
