"""Pipeline bubble micro-benchmark (VERDICT round-1 item 5).

Measures the compiled SPMD executor's step time as a function of microbatch
count M and compares the per-microbatch cost against the analytic fill+drain
bubble model: a pipelined step runs T = M + S - 1 ticks, so

    t(M) / M  ~  t_tick * (M + S - 1) / M,   bubble = (S-1)/(M+S-1)

(reference counterpart: docs/_posts/2020-09-09-pipeline-parallelism.md's
scaling discussion; tests/perf/adam_test.py is the repo's micro-bench idiom).

Run manually:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tests/perf/pipeline_bubble.py
"""

import time

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.runtime.pipe.compiled import (
    analytic_bubble_fraction,
    build_pipeline_loss,
    pipeline_mesh,
    stack_stage_params,
)

HID = 256
STAGES = 4


class Block(nn.Module):
    @nn.compact
    def __call__(self, x):
        h = nn.Dense(HID * 4)(x)
        return x + nn.Dense(HID)(jax.nn.relu(h))


def measure(num_micro, mb=8, iters=10):
    mod = Block()
    per_stage = [mod.init(jax.random.PRNGKey(s), jnp.ones((1, HID))) for s in range(STAGES)]
    mesh = pipeline_mesh(STAGES)
    stacked = stack_stage_params(per_stage, mesh)
    loss = jax.jit(jax.value_and_grad(build_pipeline_loss(
        lambda p, x, r: mod.apply(p, x),
        lambda aux, y, l: jnp.mean((y - l) ** 2),
        mesh, num_micro,
    )))
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(num_micro, mb, HID).astype(np.float32))
    lbl = jnp.asarray(rng.randn(num_micro, mb, HID).astype(np.float32))
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(loss(stacked, {}, x0, lbl, key))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = loss(stacked, {}, x0, lbl, key)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def measure_interpreter(num_micro, mb=8, iters=3):
    """Same workload through the PipelineEngine instruction interpreter (the
    per-instruction dispatch path) for the compiled-vs-interpreted comparison
    (VERDICT r3 item 5)."""
    import deepspeed_tpu
    from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule

    module = PipelineModule(
        [LayerSpec(Block) for _ in range(STAGES)], num_stages=STAGES,
        loss_fn=lambda y, l: jnp.mean((y - l) ** 2), partition_method="uniform",
    )
    dp = len(jax.devices()) // STAGES
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, config_params={
        "train_batch_size": mb * num_micro * dp,
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": num_micro,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "pipeline": {"executor": "interpreted"},
    })
    rng = np.random.RandomState(0)
    data = [(rng.randn(mb * dp, HID).astype(np.float32),
             rng.randn(mb * dp, HID).astype(np.float32))
            for _ in range(num_micro * (iters + 1))]
    it = iter(data)
    engine.train_batch(it)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        engine.train_batch(it)
    return (time.perf_counter() - t0) / iters


def main():
    print(f"S={STAGES} stages, block=dense {HID}x{HID * 4} MLP, fwd+bwd")
    print(f"{'M':>4} {'compiled ms':>12} {'interp ms':>10} {'speedup':>8} "
          f"{'analytic bubble':>16} {'ideal t/micro':>14}")
    base = None
    for M in (1, 2, 4, 8, 16):
        t = measure(M)
        ti = measure_interpreter(M)
        if base is None:
            # t(M=1) = S ticks; per-tick cost:
            t_tick = t / STAGES
            base = t_tick
        ideal = base * (M + STAGES - 1) / M
        print(f"{M:>4} {t * 1e3:>12.2f} {ti * 1e3:>10.2f} {ti / t:>8.1f}x "
              f"{analytic_bubble_fraction(STAGES, M):>16.3f} {ideal * 1e3:>14.2f}")


if __name__ == "__main__":
    main()
