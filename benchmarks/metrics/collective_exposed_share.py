"""Share of the traced window in which a device's core sat in a collective
(all-gather, reduce-scatter, all-reduce; for asynchronous ones the waiting
half) and ran nothing else: communication that compute did not hide."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or len(t.devices) < 2:
        return None
    return 100.0 * t.exposed_collective_s() / t.window_s
