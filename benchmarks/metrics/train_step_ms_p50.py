"""Median time of one optimizer step: host clock between the instants at
which consecutive steps' losses were ready (``block_until_ready``)."""

from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.host.get("step_ms") or [], 50)
