"""The cached keys and values, a row a (pass, layer), as a share of an Ouro
decode step's least bytes (``harness/costs_ouro.py``, from the program's
``decode_context_tokens`` over ``decode_steps``): how much of a step the
pass-indexed cache is, beside the four sweeps of the weights. A number to
know, not to lower: it grows with the contexts."""

from benchmarks.harness import costs_ouro


def read(run):
    step = costs_ouro.step_costs(run.cell.config, run.counters)
    if step is None:
        return None
    least_bytes, _flops, cache = step
    return 100.0 * cache / least_bytes
