"""Host time to dispatch a decode step (``ServingMetrics``:
``decode_dispatch_s``, from ``ServingEngine.launched("decode")`` to the
start of the blocking read, or to the call's end where a step in flight has
nothing to read yet, over ``decode_steps``): the side of a decode call
before the device starts on it."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps or "decode_dispatch_s" not in run.counters:
        return None
    return 1e3 * run.counters["decode_dispatch_s"] / steps
