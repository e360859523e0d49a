"""Long-sequence encoder with block-sparse attention (BigBird/Fixed layouts).

Reference analogue: ``docs/_tutorials/sparse-attention.md`` +
``docs/_posts/2020-09-09-sparse-attention.md`` (10-16x longer sequences, up
to 6.3x faster execution). The attention chain is ``BertSparseSelfAttention``
(QKV projection + ``SparseSelfAttention``), which on TPU dispatches the whole
block-sparse chain to ONE fused Pallas kernel — score blocks never hit HBM,
so cost scales with the number of live blocks, not S^2.

Smoke (CPU):  JAX_PLATFORMS=cpu python examples/sparse_attention_bert.py
Long (TPU):   python examples/sparse_attention_bert.py --seq 8192 --layout bigbird
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
from deepspeed_tpu.ops.sparse_attention import (
    BertSparseSelfAttention,
    BigBirdSparsityConfig,
    FixedSparsityConfig,
)


class LongDocEncoder(nn.Module):
    """N sparse-attention encoder layers + mean-pool classifier;
    forward(ids, y) returns scalar CE loss."""

    vocab: int
    hidden: int
    heads: int
    layers: int
    sparsity_config: object

    @nn.compact
    def __call__(self, ids, y):
        h = nn.Embed(self.vocab, self.hidden)(ids)
        for _ in range(self.layers):
            a = BertSparseSelfAttention(
                hidden_size=self.hidden, num_attention_heads=self.heads,
                sparsity_config=self.sparsity_config,
            )(nn.LayerNorm()(h))
            h = h + nn.Dense(self.hidden)(a)
            f = nn.Dense(self.hidden)(nn.gelu(nn.Dense(2 * self.hidden)(nn.LayerNorm()(h))))
            h = h + f
        logits = nn.Dense(2)(h.mean(axis=1))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--layout", choices=("fixed", "bigbird"), default="fixed")
    p.add_argument("--block", type=int, default=16,
                   help="sparsity block size (128 on TPU for MXU-aligned tiles)")
    args = p.parse_args(argv)

    heads = 4
    if args.layout == "bigbird":
        sparsity = BigBirdSparsityConfig(num_heads=heads, block=args.block)
    else:
        sparsity = FixedSparsityConfig(num_heads=heads, block=args.block)
    nb = args.seq // args.block
    live = int(sparsity.make_layout(args.seq).sum())
    print(f"{args.layout} layout: {live}/{heads * nb * nb} blocks live "
          f"({100.0 * live / (heads * nb * nb):.1f}% of dense)")

    model = LongDocEncoder(vocab=512, hidden=64, heads=heads, layers=2,
                           sparsity_config=sparsity)
    rng = np.random.RandomState(0)
    n_dev = len(jax.devices())
    global_batch = args.batch * n_dev
    ids0 = jnp.zeros((global_batch, args.seq), jnp.int32)
    y0 = jnp.zeros((global_batch,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0, y0)

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": args.batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        },
    )

    # learnable signal: class shifts the token distribution
    ys = rng.randint(0, 2, (4, global_batch)).astype(np.int32)
    idss = (rng.randint(0, 256, (4, global_batch, args.seq)) + ys[:, :, None] * 128
            ).astype(np.int32)

    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        loss = engine(jnp.asarray(idss[i % 4]), jnp.asarray(ys[i % 4]))
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    dt = time.perf_counter() - t0

    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"({args.steps * global_batch * args.seq / dt:.0f} tokens/sec)")
    assert losses[-1] < losses[0], "loss did not decrease"
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
