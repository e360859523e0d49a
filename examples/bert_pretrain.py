"""BERT pretraining (MLM + NSP) under ZeRO + bf16 + activation remat.

Reference analogue: DeepSpeedExamples/bing_bert, the subject of the
reference's headline benchmark (64 Tflops / ~272 samples/sec @ seq128 on one
V100, ``docs/_posts/2020-05-28-fastest-bert-training.md``) and of
``docs/_tutorials/bert-pretraining.md``. ``bench.py`` at the repo root is the
measured version of this script; this one is the user-facing loop.

Smoke (CPU):   JAX_PLATFORMS=cpu python examples/bert_pretrain.py
Real  (TPU):   python examples/bert_pretrain.py --large --batch 64 --steps 50
ZeRO-3:        add --zero 3 — params are STORED sharded along the data axis
               between steps (~1/dp per-device footprint) and gathered on
               use (docs/zero.md).
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
from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining


def synthetic_batch(cfg, global_batch, seq_len, seed=0):
    rng = np.random.RandomState(seed)
    input_ids = rng.randint(0, cfg.vocab_size, (global_batch, seq_len)).astype(np.int32)
    token_type_ids = np.zeros((global_batch, seq_len), np.int32)
    attention_mask = np.ones((global_batch, seq_len), np.int32)
    masked_lm_labels = np.where(
        rng.rand(global_batch, seq_len) < 0.15,
        rng.randint(0, cfg.vocab_size, (global_batch, seq_len)), -1,
    ).astype(np.int32)
    next_sentence_label = rng.randint(0, 2, (global_batch,)).astype(np.int32)
    return tuple(jnp.asarray(a) for a in (
        input_ids, token_type_ids, attention_mask, masked_lm_labels, next_sentence_label
    ))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--batch", type=int, default=2, help="micro-batch per device")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--large", action="store_true", help="BERT-large (default: tiny)")
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--zero", type=int, default=None, choices=(0, 1, 2, 3),
                   help="ZeRO stage (default: 2 on multi-device, 0 single)")
    args = p.parse_args(argv)

    if args.large:
        cfg = BertConfig.bert_large(checkpoint_policy="dots")
    else:
        cfg = BertConfig.bert_base(
            vocab_size=2048, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=128,
            checkpoint_policy="dots",
        )
    model = BertForPreTraining(cfg)

    n_dev = len(jax.devices())
    global_batch = args.batch * n_dev
    batch = synthetic_batch(cfg, global_batch, args.seq)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, *batch
    )

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": args.batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": args.lr}},
            "bf16": {"enabled": True},
            "zero_optimization": {
                "stage": args.zero if args.zero is not None
                else (2 if n_dev > 1 else 0)
            },
            "activation_checkpointing": {"enabled": True},
        },
    )

    losses = []
    t0 = time.perf_counter()
    for _ in range(args.steps):
        # fused path: scan over microbatches + optimizer update, one dispatch
        loss = engine.train_step([batch])
        losses.append(float(jax.device_get(loss)))
    dt = time.perf_counter() - t0

    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
          f"({args.steps * global_batch / dt:.1f} samples/sec on {n_dev} device(s))")
    assert losses[-1] < losses[0], "loss did not decrease"
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
