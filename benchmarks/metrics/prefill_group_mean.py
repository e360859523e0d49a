"""Requests admitted per prefill call in the window (``ServingMetrics``:
admitted prompts over prefill calls)."""


def read(run):
    calls = run.counters.get("prefill_calls", 0)
    if not calls:
        return None
    admitted = sum(v for k, v in run.counters.items()
                   if k.startswith("admitted_prompts_bucket_"))
    return admitted / calls
