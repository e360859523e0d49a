"""Least time one Laguna decode step could take over the time it took: the
step's least bytes (non-expert weights and the head once, the experts the
step touched from ``moe_experts_touched`` at three matrices each, the full
layers' keys and values of the positions the active lanes hold from
``decode_context_tokens``, the window layers' rings of the active lanes)
over the chip's memory bandwidth, or its FLOPs over peak compute, whichever
is larger, divided by the median device time of
``jit__laguna_decode_step_jit``. Bytes and FLOPs from shapes
(``harness/costs_laguna.py``)."""

from benchmarks.harness import costs, costs_laguna, stats

PROGRAM = "jit__laguna_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    means = costs_laguna.step_means(run.counters)
    if not xs or means is None:
        return None
    lanes, context, touched, picks = means
    cfg = run.cell.config
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    peaks = costs.peaks_for(run.device_kind)
    least = max(
        costs_laguna.decode_step_min_bytes(
            cfg, lanes=lanes, experts_touched=touched,
            context_tokens=context, weight_bytes=width)
        / peaks["hbm_bytes_per_s"],
        costs_laguna.decode_step_flops(
            cfg, lanes=lanes, picks=picks, context_tokens=context)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
