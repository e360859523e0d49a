"""Share of the positions a Keye-VL decode step's indexers scored that its
attention then read (``ServingMetrics``: ``dsa_keys_attended`` over
``dsa_keys_scored``, each summed over decode steps, layers and active
lanes; a lane attends ``min(context, topk)``). How much of its context a
step attends at this traffic: a number to know, not one to lower."""


def read(run):
    scored = run.counters.get("dsa_keys_scored", 0)
    if not scored:
        return None
    return 100.0 * run.counters.get("dsa_keys_attended", 0) / scored
