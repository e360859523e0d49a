"""Pages of the pool in use, on average over the decode steps of the window,
as a share of the pages the pool has (``ServingMetrics``:
``pool_pages_in_use_steps`` over ``decode_steps``, over ``kv_pool_tokens`` /
``kv_page_tokens``): how near the page budget is to binding. Read only where
the program counts it and the configuration sets a budget."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    serving = run.cell.config["serving"]
    if not steps or "pool_pages_in_use_steps" not in run.counters or (
            "kv_pool_tokens" not in serving):
        return None
    pages = serving["kv_pool_tokens"] // serving["kv_page_tokens"]
    return 100.0 * run.counters["pool_pages_in_use_steps"] / steps / pages
