"""Least time one GLM-5.2 decode step could take over the time it took: the
step's least bytes (non-expert weights and the held rows of the head once,
the experts the step touched from ``moe_experts_touched`` at three matrices
each, the indexer's keys of every position the active lanes hold in the two
layers that select from ``dsa_keys_scored`` at 256 B a position a layer, the
latent rows of the positions selected in all six layers from
``dsa_keys_attended`` at 1,152 B a position a layer) over the chip's memory
bandwidth, or its FLOPs over peak compute, whichever is larger, divided by
the median device time of ``jit__glm_decode_step_jit``. Bytes and FLOPs from
shapes (``harness/costs_glm_dsa.py``): the count reads the same work
whatever implements it. A step that read every latent row of its lanes'
contexts, or scored in a layer that shares, would owe no more by this count
and take longer: it reads low here, not high."""

from benchmarks.harness import costs, costs_glm_dsa, stats

PROGRAM = "jit__glm_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    step = costs_glm_dsa.step_costs(run.cell.config, run.counters)
    if not xs or step is None:
        return None
    least_bytes, flops = step
    peaks = costs.peaks_for(run.device_kind)
    least = max(least_bytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
