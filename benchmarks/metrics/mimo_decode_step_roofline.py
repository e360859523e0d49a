"""Least time one MiMo-V2 decode step could take over the time it took: the
step's least bytes (non-expert weights and the held rows of the head once,
the experts the step touched from ``moe_experts_touched`` at three matrices
each, the full layers' keys and values of the positions the active lanes
hold from ``decode_context_tokens`` at 2,560 B a position a layer, the
window layers' rings as far as they are behind their masks from
``decode_ring_positions`` at 5,120 B a position) over the chip's memory
bandwidth, or its FLOPs over peak compute, whichever is larger, divided by
the median device time of ``jit__mimo_decode_step_jit``. Bytes and FLOPs
from shapes (``harness/costs_mimo_v2.py``)."""

from benchmarks.harness import costs, costs_mimo_v2, stats

PROGRAM = "jit__mimo_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    step = costs_mimo_v2.step_costs(run.cell.config, run.counters)
    if not xs or step is None:
        return None
    least_bytes, flops, _attn = step
    peaks = costs.peaks_for(run.device_kind)
    least = max(least_bytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
