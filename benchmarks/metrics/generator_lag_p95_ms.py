"""How late the load generator ran: 95th percentile of actual ``submit()``
time minus due time. A starved generator must not read as a fast server."""

from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.host.get("lag_ms") or [], 95)
