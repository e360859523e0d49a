"""The length from which ``serve_late_read_share`` and
``serve_decode_read_ms`` took a plain decode read of this run as late
(``harness/read_account.py``: twice the upper edge of the median read's
bucket, so one of 0.5, 1, 2, ... 1024 ms). It halves or doubles when the
median read crosses a bucket's edge: where two runs' values differ, their
late shares are not of the same thing. Nothing where the median lies above
the last edge and no read can be late."""

import math

from benchmarks.harness import read_account


def read(run):
    rows = read_account.buckets(run.counters)
    if rows is None:
        return None
    late_from = read_account.late_from_us(rows)
    return None if math.isinf(late_from) else late_from / 1e3
