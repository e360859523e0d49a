"""Least time one decode step could take over the time it took: every
weight read once plus the live keys and values of the active lanes, over the
chip's memory bandwidth (or the step's FLOPs over peak compute, whichever is
larger: at these batch sizes memory binds), divided by the median device
time of ``jit__decode_step_jit``. Bytes and FLOPs from shapes
(``harness/costs.py``)."""

from benchmarks.harness import costs, stats

PROGRAM = "jit__decode_step_jit"


def read(run):
    if run.trace is None:
        return None
    xs = run.trace.program_durations(PROGRAM)
    steps = run.counters.get("decode_steps", 0)
    if not xs or not steps:
        return None
    cfg = run.cell.config
    lanes = run.counters.get("tokens_emitted", 0) / steps
    live = lanes * run.host["mean_live_kv_tokens_per_lane"]
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    kv_width = {"bf16": 2, "fp32": width, "int8": 1}[
        cfg["serving"]["kv_cache_dtype"]]
    shape = dict(hidden=cfg["hidden_size"],
                 intermediate=4 * cfg["hidden_size"],
                 layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"])
    peaks = costs.peaks_for(run.device_kind)
    least = max(
        costs.decode_step_min_bytes(live_kv_tokens=live, weight_bytes=width,
                                    kv_bytes=kv_width, **shape)
        / peaks["hbm_bytes_per_s"],
        costs.decode_step_flops(lanes=lanes, live_kv_tokens=live, **shape)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / stats.percentile(xs, 50)
