"""Tiny CNN on synthetic CIFAR-shaped data — the smallest end-to-end engine run.

Reference analogue: DeepSpeedExamples/cifar (the reference's introductory
tutorial model, driven through ``deepspeed.initialize`` + forward/backward/
step). Demonstrates the basic engine loop, and with ``--offload`` the
ZeRO-Offload path (host-resident fp32 master + C++/OpenMP Adam,
reference ``deepspeed/ops/adam/cpu_adam.py``).

    JAX_PLATFORMS=cpu python examples/cifar_cnn.py
"""

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn

import os
# allow `python examples/<script>.py` from anywhere: the scripts live
# one level below the repo root that holds deepspeed_tpu/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import deepspeed_tpu


class CifarCNN(nn.Module):
    """conv-relu-pool x2 -> dense; forward(x, y) returns scalar CE loss."""

    num_classes: int = 10

    @nn.compact
    def __call__(self, x, y):
        for feats in (32, 64):
            x = nn.Conv(feats, (3, 3))(x)
            x = nn.relu(x)
            x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(128)(x))
        logits = nn.Dense(self.num_classes)(x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=32, help="micro-batch per device")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--offload", action="store_true",
                   help="ZeRO-2 + cpu_offload: optimizer state on host, C++ Adam")
    args = p.parse_args(argv)

    n_dev = len(jax.devices())
    model = CifarCNN()
    x0 = jnp.zeros((args.batch * n_dev, 32, 32, 3), jnp.float32)
    y0 = jnp.zeros((args.batch * n_dev,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x0, y0)

    ds_config = {
        "train_batch_size": args.batch * n_dev,
        "train_micro_batch_size_per_gpu": args.batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": args.lr}},
        "steps_per_print": max(1, args.steps // 5),
    }
    if args.offload:
        ds_config["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=ds_config
    )

    rng = np.random.RandomState(0)
    # fixed synthetic "dataset": class-dependent means make it learnable
    xs = rng.randn(8, args.batch * n_dev, 32, 32, 3).astype(np.float32)
    ys = rng.randint(0, 10, (8, args.batch * n_dev)).astype(np.int32)
    xs += ys[:, :, None, None, None] * 0.1

    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        x, y = jnp.asarray(xs[i % 8]), jnp.asarray(ys[i % 8])
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    dt = time.perf_counter() - t0

    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"({args.steps * args.batch * n_dev / dt:.1f} samples/sec)")
    assert losses[-1] < losses[0], "loss did not decrease"
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
