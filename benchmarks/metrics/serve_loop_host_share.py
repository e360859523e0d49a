"""Share of the serving loop's busy wall time that no device program
accounts for (``ServingMetrics``: ``loop_busy_s`` less the decode steps'
dispatch-to-read-back time and the prefills' dispatch-to-read-back time,
over ``loop_busy_s``): scheduling, uploads, installs, emit and retire."""


def read(run):
    busy = run.counters.get("loop_busy_s", 0)
    if not busy:
        return None
    device = (run.counters.get("decode_time_s", 0)
              + run.counters.get("prefill_time_s", 0))
    return 100.0 * (busy - device) / busy
