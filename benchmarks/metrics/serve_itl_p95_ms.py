"""95th percentile of the pooled token gaps. Decides nothing: the gaps are
bimodal and this percentile sits on the edge of the stalled mode (PERF.md,
PR 22's refusal); ``serve_itl_tail_ms`` is the end-to-end tail."""

from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.host.get("gaps_ms") or [], 95)
