"""What the two kinds of cell share: counting compilations, reading the
per-layer metrics through their reader files, and the result line."""

import importlib
import statistics

import numpy as np

from benchmarks.harness import spec as spec_mod

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts programs that enter JAX's compile pipeline (whether the
    persistent cache then serves them or not). A window may see none."""

    _count = 0
    _registered = False

    @classmethod
    def install(cls):
        if cls._registered:
            return
        import jax

        def on_event(name, *_a, **_k):
            if name == _LOWERED:
                cls._count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._registered = True

    @classmethod
    def read(cls):
        return cls._count


def load_adapter(cfg):
    return importlib.import_module("benchmarks.models." + cfg["adapter"])


def load_reference(cfg):
    return importlib.import_module("benchmarks.refs." + cfg["reference"])


class RunData:
    """What a per-layer metric's reader may look at: the cell, host-side
    measurements (``host``), the program's counters over the window
    (``counters``: after minus before), and the trace summary (``trace``,
    None in a run without a trace)."""

    def __init__(self, cell, host, counters, trace, device_kind):
        self.cell = cell
        self.host = host
        self.counters = counters
        self.trace = trace
        self.device_kind = device_kind


def read_per_layer(cell, run):
    """Each per-layer metric of the cell through its own reader file; a
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in cell.per_layer():
        reader = spec_mod.load_reader(cell.bench_dir, m["name"])
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def worst_leaf_gap(program, reference):
    """Largest gap between the program's and the reference's norm of a leaf
    (the gap between the norms, not the norm of the difference), measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger, since some leaves are all but zero."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor, 1e-30)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def sketch_gap(program, reference, scale_leaves):
    """Norm of the difference between two gradients' sketches (signed bucket
    sums, all leaves pooled) over the norm of the reference's sketch of
    ``scale_leaves``: the leaves whose gradient every token feeds, which is
    steady from seed to seed. That is the size of the rounding noise in the
    program's gradient, which a norm's gap cannot show (zero-mean noise moves
    a norm by its square). Measured against the whole gradient it would swing
    with the seed instead: the noise is the same whatever the gradient's
    size, and the part of the gradient that few rows feed swings eightfold."""
    def sq(tree, names):
        return sum(float(np.sum(np.asarray(tree[k], np.float64) ** 2))
                   for k in names)

    num = sum(float(np.sum((np.asarray(program[k], np.float64)
                            - np.asarray(reference[k], np.float64)) ** 2))
              for k in reference)
    den = sq(reference, scale_leaves)
    return (num / den) ** 0.5 if den > 0 else None


def result_line(*, correct, attempted, failed, metrics, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line
