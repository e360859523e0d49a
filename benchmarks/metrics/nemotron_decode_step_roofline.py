"""Least time one Nemotron-H decode step could take over the time it took:
the step's least bytes (non-expert weights and the head once, the held
experts the step touched from ``moe_experts_touched`` at two matrices each,
SSM state and convolution tails read and written for the active lanes, live
key and value rows once) over the chip's memory bandwidth, or its FLOPs over
peak compute, whichever is larger, divided by the median device time of
``jit__nemotron_decode_step_jit``. Bytes and FLOPs from shapes
(``harness/costs_nemotron_h.py``)."""

from benchmarks.harness import costs, costs_nemotron_h, stats

PROGRAM = "jit__nemotron_decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    steps = run.counters.get("decode_steps", 0)
    if not xs or not steps or "moe_experts_touched" not in run.counters:
        return None
    cfg = run.cell.config
    lanes = run.counters.get("tokens_emitted", 0) / steps
    live = lanes * run.host["mean_live_kv_tokens_per_lane"]
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    peaks = costs.peaks_for(run.device_kind)
    least = max(
        costs_nemotron_h.decode_step_min_bytes(
            cfg, lanes=lanes, live_kv_rows=live, weight_bytes=width,
            experts_touched=run.counters["moe_experts_touched"] / steps)
        / peaks["hbm_bytes_per_s"],
        costs_nemotron_h.decode_step_flops(
            cfg, lanes=lanes, live_kv_rows=live,
            picks_here=run.counters.get("moe_picks_here", 0) / steps)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
