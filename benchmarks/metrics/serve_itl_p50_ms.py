"""Median gap between consecutive output tokens of one request (pooled)."""

from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.host.get("gaps_ms") or [], 50)
