"""The rate sweep that finds an open-loop cell's knee, once, on the chip:

    python benchmarks/tools/sweep.py --workload gpt2_large_chat_steady \
        --rates 1.2,1.5,1.8,2.1 --seconds 25

Each rate runs the cell's own schedule generator at that rate in a window of
its own and prints tokens per second, the time to first token in the first
and the second half of the window and the requests in flight at its start and
end: above the knee the backlog and the first-token time grow through the
window. The cell's traffic file then carries four fifths of the highest rate
that held, as a number. Chip or nothing."""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from benchmarks.harness import device as device_mod
    from benchmarks.harness import serve_cell
    from benchmarks.harness import spec as spec_mod

    cell = spec_mod.load_cell(ROOT, args.workload)
    devices = device_mod.require_chips(cell.chips)
    device_mod.enable_compile_cache(ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["arrivals"]["rate_per_s"] = rate
        line = serve_cell.run(cell, args.seed, args.seconds, False,
                              time.perf_counter(), devices)
        gc.collect()
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "seconds": args.seconds,
            "tokens_per_s": line["metrics"]["serve_tokens_per_s"]["value"],
            "failed": line["failed"], "attempted": line["attempted"],
            "correct": line["correct"], **line["diag"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
