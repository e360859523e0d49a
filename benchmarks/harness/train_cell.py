"""One run of a training cell.

Set-up builds ONE object (the compiled step with its state), drives it from
the seed through its first steps on rows that all differ, and hands that same
object to the window. After the window the plain reference follows the same
first steps from the same weights and batches, and ``correct`` compares each
step's loss, the first gradient as the optimizer got it (from Adam's first
moment after one step) and the parameters' change, by the worst leaf.
"""

import gc
import json
import os
import sys
import time

from benchmarks.harness import check as check_mod
from benchmarks.harness import device as device_mod
from benchmarks.harness import runtime, tracing
from benchmarks.harness import traffic as traffic_mod
from benchmarks.refs import weights as weights_mod

CHECK_STEPS = 3


def set_up(cell, seed, devices):
    """Weights from the seed, the program, its first CHECK_STEPS steps and
    what they read back. Returns (program, the batch feed, the first
    batches, what was observed)."""
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    ref = runtime.load_reference(cfg)
    adapter = runtime.load_adapter(cfg)
    shapes = ref.weight_shapes(cfg)
    program = adapter.Program(
        cfg, traffic, len(devices),
        weights_mod.make_weights(shapes, seed, jnp.float32))
    batches = traffic_mod.pretrain_batches(
        traffic, cfg["vocab_size"], program.global_batch, seed)
    first = [next(batches) for _ in range(CHECK_STEPS)]
    losses = []
    for i, batch in enumerate(first):
        losses.append(float(program.step(batch)))
        if i == 0:
            moment = program.first_moment_norms()
            moment_sketch = program.first_moment_sketch()
    beta1 = cfg["training"]["optimizer"]["betas"][0]
    initial = weights_mod.make_weights(shapes, seed, jnp.float32)
    observed = {
        "losses": losses,
        "first_grad_norms": {k: v / (1.0 - beta1) for k, v in moment.items()},
        "first_grad_sketch": {k: v / (1.0 - beta1)
                              for k, v in moment_sketch.items()},
        "change_norms": program.change_norms(initial, ref.change_skip(cfg)),
    }
    del initial
    return program, batches, first, observed


def window(program, batches, seconds, on_mark=None):
    """Whole steps on a fresh batch each, one step in flight behind the one
    being dispatched, until ``seconds`` have passed; ends in
    ``block_until_ready`` on the last step's loss. Returns (steps, window
    seconds, the window's first instant, the instants at which each step's
    loss was ready).
    ``on_mark(elapsed)`` is called after each completed step (the traced
    run starts its trace from it)."""
    ready = []
    t0 = time.perf_counter()
    pending = program.step(next(batches))
    steps = 1
    while True:
        nxt = program.step(next(batches))
        steps += 1
        pending.block_until_ready()
        now = time.perf_counter()
        ready.append(now)
        pending = nxt
        if on_mark is not None:
            on_mark(now - t0)
        if now - t0 >= seconds:
            break
    pending.block_until_ready()
    t1 = time.perf_counter()
    ready.append(t1)
    return steps, t1 - t0, t0, ready


def compare(cell, observed, first_batches, seed, precision="f32", out=sys.stderr):
    """The reference follows the first steps; every number beside its
    limit."""
    import jax.numpy as jnp

    cfg = cell.config
    ref = runtime.load_reference(cfg)
    weights = weights_mod.make_weights(ref.weight_shapes(cfg), seed,
                                       jnp.float32)
    expected = ref.follow(
        weights, first_batches, cfg["num_attention_heads"],
        cfg["training"]["optimizer"], ref.change_skip(cfg),
        precision=precision, rows_per_block=cfg["check"]["reference_rows_per_block"])
    limits = cell.limits
    cmp = check_mod.Comparison()
    for i, (got, want) in enumerate(zip(observed["losses"],
                                        expected["losses"])):
        cmp.add(f"loss_gap_step{i + 1}", abs(got - want) / abs(want),
                limits["loss_gap"][i], f"program {got:.6g} reference {want:.6g}")
    gap, where = runtime.worst_leaf_gap(observed["first_grad_norms"],
                                        expected["first_grad_norms"])
    cmp.add("first_grad_norm_gap", gap, limits["first_grad_norm_gap"], where)
    cmp.add("first_grad_sketch_gap",
            runtime.sketch_gap(observed["first_grad_sketch"],
                               expected["first_grad_sketch"],
                               ref.STEADY_LEAVES),
            limits["first_grad_sketch_gap"],
            "noise over the norm of " + ref.STEADY_LEAVES[0].rsplit("/", 2)[-2]
            + " and the like")
    gap, where = runtime.worst_leaf_gap(observed["change_norms"],
                                        expected["change_norms"])
    cmp.add("change_norm_gap", gap, limits["change_norm_gap"], where)
    cmp.print(out)
    print("[leaves] " + json.dumps({
        k: [observed["first_grad_norms"][k], expected["first_grad_norms"][k],
            observed["change_norms"][k], expected["change_norms"][k],
            runtime.sketch_gap({k: observed["first_grad_sketch"][k]},
                               {k: expected["first_grad_sketch"][k]}, [k])]
        for k in expected["first_grad_norms"]}), file=out, flush=True)
    return cmp, expected


def run(cell, seed, seconds, trace, t_start, devices, out=sys.stderr):
    runtime.CompileCounter.install()
    cfg, traffic = cell.config, cell.traffic
    program, batches, first, observed = set_up(cell, seed, devices)
    tokens_per_step = program.global_batch * int(traffic["seq_len"])

    capture = None
    trace_from = max(0.0, seconds - float(traffic["trace_seconds"]))

    def on_mark(elapsed):
        nonlocal capture
        if trace and capture is None and elapsed >= trace_from:
            capture = tracing.Capture(os.path.join(cell.root, ".bench_trace"))
            capture.start()

    gc.collect()
    gc.freeze()
    compiled_before = runtime.CompileCounter.read()
    setup_s = time.perf_counter() - t_start
    steps, window_s, t0, ready = window(program, batches, seconds, on_mark)
    compiled = runtime.CompileCounter.read() - compiled_before
    if capture is not None:
        capture.stop()
    gc.unfreeze()
    peak = device_mod.memory_peak_bytes(devices)
    print(f"[window] set-up {setup_s:.2f}s; {steps} steps in {window_s:.3f}s, "
          f"{compiled} programs compiled inside it; peak {peak} bytes",
          file=out, flush=True)

    program.close()
    del program
    gc.collect()
    t_ref = time.perf_counter()
    cmp, _ = compare(cell, observed, first, seed, out=out)
    cmp.add("compiled_in_window", compiled, 0)
    print(f"[check] reference took {time.perf_counter() - t_ref:.1f}s",
          file=out, flush=True)

    rate = steps * tokens_per_step / window_s / len(devices)
    device = device_mod.device_info(devices)
    device["memory_peak_bytes"] = peak
    if not trace:
        metrics = {}
        values = {"train_tokens_per_s_chip": rate, "setup_s": setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        line = runtime.result_line(correct=cmp.correct, attempted=steps,
                                   failed=0, metrics=metrics, device=device)
        line["check"] = cmp.as_dict()
        return line

    summary = tracing.TraceSummary(capture.events if capture else [])
    step_ms = [1e3 * (b - a) for a, b in zip(ready, ready[1:])]
    host = {"step_ms": step_ms, "tokens_per_s_chip": rate,
            "tokens_per_step": tokens_per_step, "window_s": window_s}
    run_data = runtime.RunData(cell, host, {}, summary, device["kind"])
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    return runtime.result_line(
        correct=cmp.correct, attempted=steps, failed=0,
        metrics=runtime.read_per_layer(cell, run_data), device=device,
        breakdown={"device_ops": summary.top_ops(10),
                   "idle_gaps": summary.idle_gaps(10)})
