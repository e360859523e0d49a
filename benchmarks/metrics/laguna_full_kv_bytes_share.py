"""The full-attention layers' keys and values as a share of a Laguna decode
step's least bytes (``harness/costs_laguna.py``, from the program's
``decode_context_tokens``, ``moe_experts_touched``, ``tokens_emitted`` over
``decode_steps``): how much of a step the long contexts are, beside the
experts, the rings and the other weights."""

from benchmarks.harness import costs_laguna


def read(run):
    means = costs_laguna.step_means(run.counters)
    if means is None:
        return None
    lanes, context, touched, _picks = means
    cfg = run.cell.config
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    return 100.0 * costs_laguna.full_kv_bytes(
        cfg, context_tokens=context, kv_bytes=width
    ) / costs_laguna.decode_step_min_bytes(
        cfg, lanes=lanes, experts_touched=touched, context_tokens=context,
        weight_bytes=width)
