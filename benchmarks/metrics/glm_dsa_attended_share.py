"""Share of the positions a GLM-5.2 decode step's indexers scored that
attention then read IN THE LAYERS THAT SCORED THEM (``ServingMetrics``:
``dsa_keys_attended`` less ``dsa_layers_shared_attended``, over
``dsa_keys_scored``; each summed over decode steps, layers and active lanes;
a lane attends ``min(context, index_topk)``). How much of its context a step
attends at this traffic, comparable with ``keye_dsa_attended_share``: a
number to know, not one to lower."""


def read(run):
    scored = run.counters.get("dsa_keys_scored", 0)
    if not scored or "dsa_layers_shared_attended" not in run.counters:
        return None
    own = (run.counters.get("dsa_keys_attended", 0)
           - run.counters["dsa_layers_shared_attended"])
    return 100.0 * own / scored
