"""Share of the window in which the loop KNEW the device had no model
program queued (``ServingMetrics``: ``dry_after_decode_s`` +
``dry_after_prefill_s``, from the return of a read of the newest program
dispatched to the next ``launched``, over ``run.host["window_s"]``): emit
and retire behind a synchronous step, the first tokens, installs or lane
patch and upload behind a prefill. On the host's clock, over the whole
window, and a lower bound of ``serve_device_idle_share`` (the trace's last
seconds), which adds launch latency and, for a family with a step in
flight, idleness the host cannot prove."""


def read(run):
    window = run.host.get("window_s")
    keys = ("dry_after_decode_s", "dry_after_prefill_s")
    if not window or any(k not in run.counters for k in keys):
        return None
    return 100.0 * sum(run.counters[k] for k in keys) / window
