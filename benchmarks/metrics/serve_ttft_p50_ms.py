"""Median time to first token, from the instant a request was DUE (not from
when the generator got round to sending it) to its first ``stream_cb``."""

from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.host.get("ttft_ms") or [], 50)
