"""GPT-2 language modeling with pipeline parallelism + ZeRO-1.

Reference analogue: the Megatron GPT-2 scripts in DeepSpeedExamples driven by
``tests/model/Megatron_GPT2`` and ``docs/_posts/2020-09-09-pipeline-parallelism.md``
(3D parallelism). The model is built as a ``PipelineModule`` layer list with
tied embedding/head (``TiedLayerSpec``); stages are jitted over per-stage mesh
slices, with ZeRO-1 sharding the optimizer state inside each stage's data
axis.

Smoke (8-dev CPU mesh, pp2 x dp4):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/gpt2_pipeline.py
Full GPT-2 1.5B: --xl --stages 8 --seq 1024 (needs a multi-chip mesh).
"""

import argparse
import sys
import time

import numpy as np

import jax

import os
# allow `python examples/<script>.py` from anywhere: the scripts live
# one level below the repo root that holds deepspeed_tpu/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.models.gpt2_pipe import build_gpt2_pipeline


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--batch", type=int, default=4, help="micro-batch size")
    p.add_argument("--gas", type=int, default=2, help="microbatches per step")
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--stages", type=int, default=2, help="pipeline stages")
    p.add_argument("--zero", type=int, default=1, choices=(0, 1, 2))
    p.add_argument("--xl", action="store_true", help="GPT-2 1.5B (default: tiny)")
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, restack the pipeline params and "
                        "greedy-decode N tokens (inference/convert.py)")
    args = p.parse_args(argv)

    if args.xl:
        cfg = GPT2Config.gpt2_xl()
    else:
        cfg = GPT2Config(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=2, max_position_embeddings=max(64, args.seq),
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        )

    n_dev = len(jax.devices())
    assert n_dev % args.stages == 0, f"{n_dev} devices not divisible by {args.stages} stages"
    dp = n_dev // args.stages

    module = build_gpt2_pipeline(cfg, num_stages=args.stages, partition_method="parameters")
    engine, _, _, _ = deepspeed_tpu.initialize(model=module, config_params={
        "train_batch_size": args.batch * args.gas * dp,
        "train_micro_batch_size_per_gpu": args.batch,
        "gradient_accumulation_steps": args.gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": args.zero},
    })

    # skewed token distribution so the LM loss can drop below ln(vocab)
    rng = np.random.RandomState(0)
    def batches():
        while True:
            ids = rng.randint(0, 32, (args.batch * dp, args.seq)).astype(np.int32)
            yield ids, ids
    it = batches()

    losses = []
    t0 = time.perf_counter()
    for _ in range(args.steps):
        losses.append(float(engine.train_batch(it)))
    dt = time.perf_counter() - t0

    tokens = args.steps * args.batch * args.gas * dp * args.seq
    print(f"pp{args.stages} x dp{dp}, ZeRO-{args.zero}  "
          f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}  ({tokens / dt:.0f} tokens/sec)")
    assert losses[-1] < losses[0], "loss did not decrease"

    if args.generate:
        # train -> serve: restack the pipeline layers into the decode layout
        # (inference/convert.py) and sample a continuation
        from deepspeed_tpu.inference import generate, pipe_layers_to_lm_params

        engine._sync_from_compiled()
        layers = [jax.device_get(p) if p is not None else None
                  for p in engine._gather_layer_params()]
        params = pipe_layers_to_lm_params(layers)
        prompt = np.asarray(rng.randint(0, 32, (1, 8)), np.int32)
        toks = generate(params, cfg, prompt, args.generate)
        print(f"generated {args.generate} tokens from the trained pipeline: "
              f"{np.asarray(toks)[0].tolist()}")
    return 0


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
