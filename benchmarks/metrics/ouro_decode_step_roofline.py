"""Least time one Ouro decode step could take over the time it took: the
step's least bytes (``total_ut_steps`` sweeps of the layers' weights, the
head once, and the keys and values of the positions the active lanes hold
from ``decode_context_tokens`` over ``decode_steps`` at 1,572,864 B a
position: a row a (pass, layer)) over the chip's memory bandwidth, or its
FLOPs over peak compute, whichever is larger, divided by the median device
time of ``jit__ouro_decode_step_jit``. It counts four sweeps of the weights
whatever implements the step. Bytes and FLOPs from shapes
(``harness/costs_ouro.py``)."""

from benchmarks.harness import costs, costs_ouro, stats

PROGRAM = "jit__ouro_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    step = costs_ouro.step_costs(run.cell.config, run.counters)
    if not xs or step is None:
        return None
    least_bytes, flops, _cache = step
    peaks = costs.peaks_for(run.device_kind)
    least = max(least_bytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
