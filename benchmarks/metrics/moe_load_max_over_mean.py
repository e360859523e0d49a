"""The busiest held expert's tokens over the mean tokens of a held expert,
a layer a decode step (``ServingMetrics``: ``moe_expert_load_max`` times the
experts held over ``moe_picks_here``): 1 is perfect balance."""


def read(run):
    picks = run.counters.get("moe_picks_here", 0)
    if not picks:
        return None
    held = run.cell.config["num_experts"]
    return run.counters.get("moe_expert_load_max", 0) * held / picks
