"""Read the numbers a cell's limits are set from (PERF.md records them):

    python benchmarks/tools/limits.py --workload <cell> --seeds 1,2,3 \
        [--seconds S] [--control fp8]

For each seed it prints what ``correct`` compares. A training cell needs no
measured window: set-up drives the program through its first steps, the
reference follows. A serving cell runs a short window at the cell's own
load, long enough to finish the mix's longest requests. With ``--control``
the reference is put in the program's place at that lower precision: for
training the reference's own three steps are followed at that precision and
compared like a program's; for serving the tokens that precision would put
first are read at each position of the same prompts and served tokens.
Chip or nothing, like the benchmark itself."""

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


def train_numbers(cell, seed, devices, control):
    import jax.numpy as jnp

    from benchmarks.harness import runtime, train_cell
    from benchmarks.harness import traffic as traffic_mod
    from benchmarks.refs import weights as weights_mod

    cfg = cell.config
    if control is None:
        program, _b, first, observed = train_cell.set_up(cell, seed, devices)
        program.close()
        del program
        gc.collect()
    else:
        ref = runtime.load_reference(cfg)
        batch = int(cell.traffic["micro_batch_per_chip"]) * cell.chips
        gen = traffic_mod.pretrain_batches(cell.traffic, cfg["vocab_size"],
                                           batch, seed)
        first = [next(gen) for _ in range(train_cell.CHECK_STEPS)]
        observed = ref.follow(
            weights_mod.make_weights(ref.weight_shapes(cfg), seed,
                                     jnp.float32),
            first, cfg["num_attention_heads"], cfg["training"]["optimizer"],
            ref.change_skip(cfg), precision=control,
            rows_per_block=cfg["check"]["reference_rows_per_block"])
    cmp, _ = train_cell.compare(cell, observed, first, seed)
    return cmp.as_dict()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--chips", type=int, default=None,
                    help="devices to hold (a training control needs one)")
    args = ap.parse_args(argv)

    from benchmarks.harness import device as device_mod
    from benchmarks.harness import serve_cell
    from benchmarks.harness import spec as spec_mod

    cell = spec_mod.load_cell(ROOT, args.workload)
    devices = device_mod.require_chips(args.chips or cell.chips)
    device_mod.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.config["kind"] == "train":
            numbers = train_numbers(cell, seed, devices, args.control)
        else:
            line = serve_cell.run(cell, seed, args.seconds, False,
                                  time.perf_counter(), devices,
                                  precision=args.control or "f32")
            numbers = line["check"]
            numbers["metrics"] = {k: v["value"]
                                  for k, v in line["metrics"].items()}
        gc.collect()
        print("LIMITS " + json.dumps({
            "workload": cell.name, "seed": seed, "control": args.control,
            "numbers": numbers, "took_s": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
