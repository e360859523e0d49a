"""SQuAD-style span-extraction fine-tune with 1-bit Adam.

Reference analogue: DeepSpeedExamples/BingBertSquad with the ``OneBitAdam``
optimizer (``docs/_posts/2020-09-09-onebit-adam-blog-post.md`` — up to 5x
less communication after the dense warmup). The model is
``BertForQuestionAnswering`` (start/end span logits, reference
``tests/unit/modeling.py``); after ``freeze_step`` warmup steps the engine
switches to error-compensated 1-bit compressed gradient exchange over the
mesh's data axis.

NOTE on freeze_step: real runs freeze late (the reference SQuAD recipe uses
freeze_step in the tens of thousands) so the Adam variance has converged for
every parameter before it is frozen. Freezing early leaves small-variance
components whose sign-compressed (uniform-magnitude) momentum produces huge
updates — visible here as divergence if you raise --lr with the smoke-sized
--freeze-step.

Smoke (CPU):  JAX_PLATFORMS=cpu \
              XLA_FLAGS=--xla_force_host_platform_device_count=8 \
              python examples/onebit_adam_squad.py
"""

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import os
# allow `python examples/<script>.py` from anywhere: the scripts live
# one level below the repo root that holds deepspeed_tpu/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import deepspeed_tpu
from deepspeed_tpu.models.bert import BertConfig, BertForQuestionAnswering


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=2, help="micro-batch per device")
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--freeze-step", type=int, default=6,
                   help="dense-Adam warmup steps before 1-bit compression starts")
    p.add_argument("--lr", type=float, default=3e-5)
    p.add_argument("--large", action="store_true", help="BERT-large (default: tiny)")
    args = p.parse_args(argv)

    if args.large:
        cfg = BertConfig.bert_large()
    else:
        cfg = BertConfig.bert_base(
            vocab_size=1024, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=128,
        )
    model = BertForQuestionAnswering(cfg)

    n_dev = len(jax.devices())
    global_batch = args.batch * n_dev
    ids0 = jnp.zeros((global_batch, args.seq), jnp.int32)
    pos0 = jnp.zeros((global_batch,), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids0, ids0, jnp.ones_like(ids0), pos0, pos0,
    )

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": args.batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": args.lr, "freeze_step": args.freeze_step}},
            # reference BingBertSquad configs clip at 1.0 — essential here:
            # right after freeze_step the frozen Adam variance is still small
            # and unclipped compressed updates can blow up
            "gradient_clipping": 1.0,
        },
    )

    # synthetic QA: the answer span start/end correlate with the first token id
    rng = np.random.RandomState(0)
    def make_batch(i):
        ids = rng.randint(0, cfg.vocab_size, (global_batch, args.seq)).astype(np.int32)
        start = (ids[:, 0] % (args.seq - 4)).astype(np.int32)
        end = start + (ids[:, 1] % 4).astype(np.int32)
        tt = np.zeros_like(ids)
        tt[:, args.seq // 2:] = 1  # question | context segmentation
        return tuple(jnp.asarray(a) for a in (ids, tt, np.ones_like(ids), start, end))

    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        batch = make_batch(i)
        loss = engine(*batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    dt = time.perf_counter() - t0

    compressed = max(0, args.steps - args.freeze_step)
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"({args.steps * global_batch / dt:.1f} samples/sec; "
          f"{compressed}/{args.steps} steps used 1-bit compressed comm)")
    assert np.isfinite(losses).all(), "loss diverged"
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
