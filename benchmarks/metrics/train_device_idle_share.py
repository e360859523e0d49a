"""1 - (union of device operation intervals) / traced window, averaged over
the chips."""


def read(run):
    t = run.trace
    if t is None or t.idle_share is None:
        return None
    return 100.0 * t.idle_share
