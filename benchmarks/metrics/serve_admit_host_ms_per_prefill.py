"""What an admission costs the lanes beyond its prefill program
(``ServingMetrics``: ``admit_time_s`` less ``prefill_time_s``, over
``prefill_calls``): grouping, page allocation, uploads, and the lane
installs the admission settles before it returns."""


def read(run):
    calls = run.counters.get("prefill_calls", 0)
    if not calls or "admit_time_s" not in run.counters:
        return None
    return 1e3 * (run.counters["admit_time_s"]
                  - run.counters.get("prefill_time_s", 0)) / calls
