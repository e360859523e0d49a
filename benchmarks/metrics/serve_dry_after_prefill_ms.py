"""Mean dry spell behind a prefill call (``ServingMetrics``:
``dry_after_prefill_s`` over ``dry_spells_after_prefill``, from the return
of the first tokens' read to the next program's launch): the admission tail,
first tokens handed out, lanes installed or patched, lane state uploaded,
in which the device has nothing to run."""


def read(run):
    spells = run.counters.get("dry_spells_after_prefill", 0)
    if not spells or "dry_after_prefill_s" not in run.counters:
        return None
    return 1e3 * run.counters["dry_after_prefill_s"] / spells
