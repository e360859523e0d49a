"""The cached keys and values, the full layers' pages and the window layers'
rings together, as a share of a MiMo-V2 decode step's least bytes
(``harness/costs_mimo_v2.py``, from the program's ``decode_context_tokens``,
``decode_ring_positions`` and ``moe_experts_touched`` over
``decode_steps``): how much of a step the contexts are, beside the experts
and the other weights."""

from benchmarks.harness import costs_mimo_v2


def read(run):
    step = costs_mimo_v2.step_costs(run.cell.config, run.counters)
    if step is None:
        return None
    least_bytes, _flops, attn = step
    return 100.0 * attn / least_bytes
