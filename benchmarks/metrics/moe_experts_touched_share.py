"""Share of the held experts that at least one token of a decode step was
routed to, a layer a step (``ServingMetrics``: ``moe_experts_touched`` over
``moe_layer_steps`` times the experts held). It is what a step's expert
bytes scale with."""


def read(run):
    layer_steps = run.counters.get("moe_layer_steps", 0)
    if not layer_steps:
        return None
    held = run.cell.config["num_experts"]
    return 100.0 * run.counters.get("moe_experts_touched", 0) / (
        layer_steps * held)
