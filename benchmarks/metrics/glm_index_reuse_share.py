"""Of the layer-positions a GLM-5.2 decode step attended, the share attended
under a selection that ANOTHER layer computed (``ServingMetrics``:
``dsa_layers_shared_attended`` over ``dsa_keys_attended``, each summed over
decode steps, layers and active lanes): 4 layers of 6 in the cell's cut, 57
of 78 as published. What the indexers a step does not run would have scored:
a number to know, not one to lower."""


def read(run):
    attended = run.counters.get("dsa_keys_attended", 0)
    if not attended or "dsa_layers_shared_attended" not in run.counters:
        return None
    return 100.0 * run.counters["dsa_layers_shared_attended"] / attended
