"""Mixture-of-Experts transformer LM with expert parallelism.

Beyond the v0.3.10 reference (which predates DeepSpeed-MoE) but a
reference-family capability: later DeepSpeed made MoE + expert parallelism
a headline feature. This example trains a small decoder LM whose FFN blocks
are Switch-style top-1 MoE layers (``deepspeed_tpu.parallel.expert``),
driven through ``deepspeed_tpu.initialize``, then demonstrates the
expert-parallel layout two ways:

1. engine loop — ``MoELayer`` inside a flax model, aux load-balancing loss
   folded into the training loss (the Switch recipe, coeff 1e-2);
2. pjit expert parallelism — the same stacked expert params laid over the
   mesh with ``expert_shardings`` (expert dim split on the ``data`` axis,
   DeepSpeed-MoE's expert-parallel-within-DP layout) so GSPMD partitions
   the dispatch/FFN/combine einsums, verified equal to the replicated run.

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/moe_transformer.py
"""

import argparse
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn

# allow `python examples/<script>.py` from anywhere: the scripts live
# one level below the repo root that holds deepspeed_tpu/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import deepspeed_tpu
from deepspeed_tpu.parallel.expert import (
    MoEConfig, MoELayer, expert_shardings, moe_ffn,
)
from deepspeed_tpu.parallel.mesh import create_mesh


class MoETransformerLM(nn.Module):
    """Decoder-only LM: attention + MoE-FFN blocks, returns mean CE loss
    (+ the scaled Switch aux loss from every MoE layer)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    num_experts: int = 8
    aux_coeff: float = 1e-2

    @nn.compact
    def __call__(self, tokens, targets):
        B, S = tokens.shape
        h = nn.Embed(self.vocab, self.d_model)(tokens)
        h = h + self.param(
            "pos", nn.initializers.normal(0.02), (S, self.d_model))[None]
        mask = nn.make_causal_mask(tokens)
        aux_total = 0.0
        for _ in range(self.n_layers):
            a = nn.LayerNorm()(h)
            a = nn.SelfAttention(num_heads=self.n_heads)(a, mask=mask)
            h = h + a
            f = nn.LayerNorm()(h)
            f, aux = MoELayer(MoEConfig(
                num_experts=self.num_experts, d_model=self.d_model,
                d_ff=4 * self.d_model))(f)
            h = h + f
            aux_total = aux_total + aux
        logits = nn.Dense(self.vocab)(nn.LayerNorm()(h))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ce = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
        return ce + self.aux_coeff * aux_total / self.n_layers


def train(args):
    # args.batch is the PER-DEVICE micro batch (the convention of every
    # example here); the global batch scales with the visible device count
    global_batch = args.batch * len(jax.devices())
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 256, (global_batch, args.seq)))
    targets = jnp.asarray(rng.randint(0, 256, (global_batch, args.seq)))

    model = MoETransformerLM(num_experts=args.experts)
    params = model.init(jax.random.PRNGKey(0), tokens, targets)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": args.batch,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": args.zero},
        })

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        loss = engine(tokens, targets)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
        print(f"step {step}: loss {losses[-1]:.4f}")
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({global_batch * args.seq * args.steps / dt:.0f} tokens/sec)")
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def expert_parallel_demo(args):
    """Same MoE math, expert dim sharded over the mesh's data axis: GSPMD
    turns the dispatch/combine einsums into the all_to_all exchange that
    ``expert_parallel_ffn`` writes by hand (see test_moe.py's HLO assert)."""
    mesh = create_mesh()
    W = mesh.shape["data"]
    # the expert dim shards over the data axis, so round it up to a multiple
    # of the axis size (the engine-loop model above has no such constraint)
    E = ((args.experts + W - 1) // W) * W
    d, f, T = 64, 256, 512
    k = jax.random.PRNGKey(1)
    ks = jax.random.split(k, 6)
    params = {
        "router": jax.random.normal(ks[0], (d, E)) * 0.02,
        "w1": jax.random.normal(ks[1], (E, d, f)) * 0.02,
        "b1": jnp.zeros((E, f)),
        "w2": jax.random.normal(ks[2], (E, f, d)) * 0.02,
        "b2": jnp.zeros((E, d)),
    }
    x = jax.random.normal(ks[3], (T, d))
    capacity = T // E

    ref, _ = jax.jit(lambda p, x: moe_ffn(p, x, capacity))(params, x)

    shardings = expert_shardings(mesh, params)
    params_ep = jax.device_put(params, shardings)
    out, _ = jax.jit(lambda p, x: moe_ffn(p, x, capacity))(params_ep, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"expert-parallel (E={E} over {mesh.shape['data']} devices) "
          f"max |Δ| vs replicated: {err:.2e}")
    assert err < 1e-4


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--zero", type=int, default=0, choices=(0, 1, 2, 3))
    args = p.parse_args(argv)
    train(args)
    if len(jax.devices()) > 1:
        expert_parallel_demo(args)
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
