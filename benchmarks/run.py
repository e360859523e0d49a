"""One run of one cell of the benchmark:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). Without an accelerator, or with fewer chips than the cell
needs, the exit code is not 0 and no result is printed: a number from a CPU
is never a device number. Everything else goes to standard error.
"""

import time

T_START = time.perf_counter()       # set-up is counted from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(root, workload, seed, seconds, trace, t_start=None,
             require_chip=True, out=sys.stderr):
    """Run one cell and return its result line as a dict. ``require_chip``
    is for the benchmark's own tests, which drive everything but the look
    for the chip (and the persistent compile cache, a process-wide setting)
    at tiny size on the CPU; the command never passes it."""
    from benchmarks.harness import device as device_mod
    from benchmarks.harness import spec as spec_mod

    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec_mod.load_cell(root, workload)
    import jax

    if require_chip:
        devices = device_mod.require_chips(cell.chips)
        cache = device_mod.enable_compile_cache(root)
    else:
        # the tests' path: a test process keeps JAX's settings as they are
        devices, cache = jax.devices()[:cell.chips], "off (test run)"
    print(f"[setup] cell {cell.name} seed {seed} on "
          f"{device_mod.device_info(devices)}; compile cache {cache}",
          file=out, flush=True)
    kind = cell.config["kind"]
    if kind == "train":
        from benchmarks.harness import train_cell as driver
    elif kind == "serve":
        from benchmarks.harness import serve_cell as driver
    else:
        raise spec_mod.SpecError(f"configuration kind {kind!r}")
    return driver.run(cell, seed, seconds, trace, t_start, devices, out=out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
