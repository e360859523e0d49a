"""Benchmark: BERT-large pretraining throughput + MFU @ seq128.

The reference's headline number is 272 samples/sec (64 Tflops, >50% of V100
peak) on 1x V100 for BERT-large seq128 pretraining under its fused kernels +
ZeRO (reference docs/_posts/2020-05-28-fastest-bert-training.md:15-16,38-39;
BASELINE.md). This harness trains the same model shape through the
deepspeed_tpu engine and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N, ...}

One process per chip: the parent never initialises a JAX backend (a parent
that has touched JAX holds the chip, and its child then fails or hangs). It
runs the measurement in a child with a hard timeout, stepping the micro-batch
down on an out-of-memory failure. The BERT and GPT-2 throughput legs measure
the chip and nothing else: a child that finds no TPU exits non-zero, and so
does the parent. No cached, replayed or CPU number is ever printed in a chip
leg's place. (The other ``BENCH_MODEL`` legs are CPU correctness-and-count
artifacts that pin ``JAX_PLATFORMS=cpu`` themselves.)
"""

import json
import os
import subprocess
import sys
import time

BASELINE_SAMPLES_PER_SEC = 272.0  # V100 reference, BERT-large seq128
BASELINE_TFLOPS = 64.0
# seq512 secondary headline (fastest-bert post :38-39)
BASELINE_SEQ512_SAMPLES_PER_SEC = 52.0
BASELINE_SEQ512_TFLOPS = 53.0


# ---------------------------------------------------------------------------
# child: the actual measurement
# ---------------------------------------------------------------------------

def _require_tpu():
    """The chip legs' own platform check (it replaces a throwaway probe
    process, which cost a whole chip initialisation): the device when it is
    a TPU, otherwise exit non-zero with nothing on stdout."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: this leg measures the chip and JAX found "
                 f"{dev.platform!r} ({dev.device_kind}); no number printed")
    return dev


def _bench_knobs(default_mb, default_seq, default_steps, default_warmup):
    """Shared env-knob surface of the chip legs (sizes never depend on the
    platform: a different size is a different measurement)."""
    return dict(
        micro_batch=int(os.environ.get("BENCH_BATCH", default_mb)),
        seq_len=int(os.environ.get("BENCH_SEQ", default_seq)),
        steps=int(os.environ.get("BENCH_STEPS", default_steps)),
        warmup=int(os.environ.get("BENCH_WARMUP", default_warmup)),
        remat=os.environ.get("BENCH_REMAT", "1") == "1",
        policy=os.environ.get("BENCH_REMAT_POLICY", "dots"),
        scan_unroll=int(os.environ.get("BENCH_SCAN_UNROLL", "1")),
    )


def _make_engine(model, params, global_batch, micro_batch, n_dev, remat):
    """One engine config for every leg: bf16 (the TPU-native precision story;
    fp16 loss scaling exists for parity but is unnecessary overhead on the
    MXU), ZeRO-2 when data-parallel, config-driven activation remat."""
    import deepspeed_tpu

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": micro_batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2 if n_dev > 1 else 0},
            "activation_checkpointing": {"enabled": remat},
        },
    )
    return engine


def _timed_chain(engine, batch, warmup, steps):
    """Measured train_step window: chain ``steps`` donated-buffer train
    steps (step i+1's params depend on step i's) with no host sync between
    them, and end the window by fetching ONE final scalar loss — the fetch
    transitively waits for the whole chain. Any future timing fix belongs
    HERE, for all legs."""
    import jax

    loss = None
    for _ in range(warmup):
        loss = engine.train_step([batch])
    if loss is not None:
        float(jax.device_get(loss))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_step([batch])
    final_loss = float(jax.device_get(loss))
    return time.perf_counter() - t0, final_loss


def _perf_fields(dt, steps, cfg, n_params, global_batch, seq_len, n_dev, dev):
    """Analytic model-FLOPs accounting shared by every leg (the standard MFU
    convention): a training step costs ~6*N FLOPs/token for the matmuls plus
    12*L*H*S FLOPs/token for attention score/value products (fwd + bwd)."""
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
    tokens = global_batch * seq_len
    achieved_tflops = flops_per_token * tokens / (dt / steps) / n_dev / 1e12
    from deepspeed_tpu.profiling.flops_profiler.profiler import device_peak_tflops

    return {
        "tflops_per_chip": round(achieved_tflops, 2),
        "mfu": round(achieved_tflops / device_peak_tflops(dev), 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": n_dev,
        "global_batch": global_batch,
        "step_ms": round(dt / steps * 1000.0, 2),
        "params": n_params,
    }


def child_main():
    if os.environ.get("BENCH_MODEL", "bert") == "gpt2":
        return gpt2_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "serving":
        return serving_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "memtier":
        return memtier_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "longdoc":
        return longdoc_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "fleet":
        return fleet_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "chaos":
        return chaos_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "rollout":
        return rollout_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "disagg":
        return disagg_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "kernels":
        return kernels_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "train":
        return train_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "offload":
        return offload_child_main()
    if os.environ.get("BENCH_MODEL", "bert") == "mesh":
        return mesh_child_main()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    dev = _require_tpu()
    platform = dev.platform
    enable_compile_cache()
    knobs = _bench_knobs("64", "128", "30", "3")
    micro_batch, seq_len = knobs["micro_batch"], knobs["seq_len"]
    n_dev = len(jax.devices())

    # Remat the encoder stack by default: without it, 24 layers of saved
    # [B,S,H] intermediates + dropout masks OOM a single chip's HBM at
    # micro-batch 64 (the round-3 failure: a 192MB pred[24,64,128,1024]
    # dropout-mask stack died in AllocateBuffer). BENCH_REMAT=0 opts out.
    # Remat is requested through the ds_config activation_checkpointing
    # section — the ENGINE flips BertConfig.checkpoint_activations
    # (per-layer scanned remat), exercising the config wiring end-to-end.
    cfg = BertConfig.bert_large(checkpoint_policy=knobs["policy"],
                            scan_unroll=knobs["scan_unroll"])
    model = BertForPreTraining(cfg)

    # The engine shards the given batch across the data axis as the GLOBAL
    # batch, so feed micro_batch * n_dev rows and count exactly that many
    # samples per step (round-1 advisor finding: counting batch*n_dev while
    # feeding batch rows inflated multi-device throughput by n_dev).
    global_batch = micro_batch * n_dev

    rng = np.random.RandomState(0)
    input_ids = rng.randint(0, cfg.vocab_size, (global_batch, seq_len)).astype(np.int32)
    token_type_ids = np.zeros((global_batch, seq_len), np.int32)
    attention_mask = np.ones((global_batch, seq_len), np.int32)
    masked_lm_labels = np.where(
        rng.rand(global_batch, seq_len) < 0.15,
        rng.randint(0, cfg.vocab_size, (global_batch, seq_len)),
        -1,
    ).astype(np.int32)
    next_sentence_label = rng.randint(0, 2, (global_batch,)).astype(np.int32)
    batch = tuple(jnp.asarray(x) for x in (
        input_ids, token_type_ids, attention_mask, masked_lm_labels, next_sentence_label
    ))

    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, *batch
    )
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

    engine = _make_engine(model, params, global_batch, micro_batch, n_dev, knobs["remat"])
    dt, final_loss = _timed_chain(engine, batch, knobs["warmup"], knobs["steps"])
    per_chip = global_batch * knobs["steps"] / dt / n_dev

    # The reference publishes baselines only for seq128 and seq512; any other
    # seq reports vs_baseline as null rather than a cross-config ratio.
    if seq_len == 128:
        base_sps, base_tf = BASELINE_SAMPLES_PER_SEC, BASELINE_TFLOPS
    elif seq_len == 512:
        base_sps, base_tf = BASELINE_SEQ512_SAMPLES_PER_SEC, BASELINE_SEQ512_TFLOPS
    else:
        base_sps = base_tf = None
    fields = _perf_fields(dt, knobs["steps"], cfg, n_params, global_batch,
                          seq_len, n_dev, dev)
    print(json.dumps({
        "metric": f"bert-large pretrain samples/sec/chip @ seq{seq_len} ({platform})",
        "value": round(per_chip, 2),
        "unit": "samples/sec",
        "vs_baseline": round(per_chip / base_sps, 3) if base_sps else None,
        "vs_baseline_tflops": (round(fields["tflops_per_chip"] / base_tf, 3)
                               if base_tf else None),
        **fields,
        "micro_batch": micro_batch,
        "remat": cfg.checkpoint_activations,
        "remat_policy": cfg.checkpoint_policy,
        "scan_unroll": cfg.scan_unroll,
        "attn_impl": _attn_impl_label(),
        "attn_rows_per_step": _attn_rows_per_step(),
        "final_loss": round(final_loss, 3),
    }))
    return 0


def gpt2_child_main():
    """Secondary flagship leg: GPT-2 causal-LM pretraining tokens/sec.

    BASELINE.json's metric names GPT-2 throughput alongside BERT; the
    reference has no published per-chip number (its GPT-2 runs drive the
    external Megatron examples), so vs_baseline is null — the value is the
    measured record itself. BENCH_GPT2_SIZE: small|medium|large|xl
    (default medium, 355M — the largest whose full Adam state fits one v5e
    chip next to seq-1024 activations)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    dev = _require_tpu()
    platform = dev.platform
    enable_compile_cache()
    size = os.environ.get("BENCH_GPT2_SIZE", "medium")
    knobs = _bench_knobs("8", "1024", "20", "2")
    micro_batch, seq_len = knobs["micro_batch"], knobs["seq_len"]
    n_dev = len(jax.devices())

    ctor = {"small": GPT2Config.gpt2_small, "medium": GPT2Config.gpt2_medium,
            "large": GPT2Config.gpt2_large, "xl": GPT2Config.gpt2_xl}[size]
    cfg = ctor(checkpoint_policy=knobs["policy"],
               scan_unroll=knobs["scan_unroll"],
               max_position_embeddings=max(1024, seq_len))
    model = GPT2LMHeadModel(cfg)
    global_batch = micro_batch * n_dev

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (global_batch, seq_len)).astype(np.int32))
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}, ids, ids
    )
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

    engine = _make_engine(model, params, global_batch, micro_batch, n_dev, knobs["remat"])
    dt, final_loss = _timed_chain(engine, (ids, ids), knobs["warmup"], knobs["steps"])
    per_chip = global_batch * seq_len * knobs["steps"] / dt / n_dev

    fields = _perf_fields(dt, knobs["steps"], cfg, n_params, global_batch,
                          seq_len, n_dev, dev)
    print(json.dumps({
        "metric": f"gpt2-{size} pretrain tokens/sec/chip @ seq{seq_len} ({platform})",
        "value": round(per_chip, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "samples_per_sec_per_chip": round(per_chip / seq_len, 3),
        **fields,
        "micro_batch": micro_batch,
        "remat": cfg.checkpoint_activations,
        "remat_policy": cfg.checkpoint_policy,
        "scan_unroll": cfg.scan_unroll,
        "attn_impl": _attn_impl_label(),
        "attn_rows_per_step": _attn_rows_per_step(),
        "final_loss": round(final_loss, 3),
    }))
    return 0


def serving_child_main():
    """Serving leg: continuous-batching aggregate tokens/sec + TTFT.

    Same tiny GPT-2 shape as tests/perf/decode_bench.py, so the aggregate
    number reads directly against that artifact's single-stream
    ``kv_cache_tok_per_s`` rows — the delta IS the continuous-batching
    win. Prompts share a system-prompt-style prefix so the prefix KV
    cache has something to hit. Writes SERVING_BENCH[_CPU].json next to
    DECODE_BENCH[_CPU].json (and prints before/after TTFT and decode
    throughput lines when a previous artifact exists) plus the usual one
    JSON line. The decode leg runs TWICE — speculation off then on — so
    the artifact carries both numbers and the accept rate. Knobs:
    BENCH_SERVE_REQUESTS / BENCH_SERVE_SLOTS / BENCH_SERVE_NEW_TOKENS /
    BENCH_SERVE_CHUNK (chunked prefill, 0=off) / BENCH_SERVE_PREFIX_MB
    (prefix cache budget, 0=off) / BENCH_SERVE_SPEC_K (self-drafted
    speculative tokens per step, 0=off) / BENCH_SERVE_KV_DTYPE
    (fp32|bf16|int8 KV-pool storage)."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    dev = jax.devices()[0]
    platform = dev.platform
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "16"))
    max_slots = int(os.environ.get("BENCH_SERVE_SLOTS", "8"))
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", "32"))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "0"))
    prefix_mb = float(os.environ.get("BENCH_SERVE_PREFIX_MB", "8"))
    spec_k = int(os.environ.get("BENCH_SERVE_SPEC_K", "4"))
    kv_dtype = os.environ.get("BENCH_SERVE_KV_DTYPE", "fp32")

    cfg = GPT2Config(
        vocab_size=512, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=4, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=1, seq_len=8, seed=0)

    rng = np.random.RandomState(0)
    system_prefix = rng.randint(0, cfg.vocab_size, (6,)).tolist()
    prompts = [system_prefix
               + rng.randint(0, cfg.vocab_size, (int(n),)).tolist()
               for n in rng.randint(1, 11, size=n_requests)]  # len 7..16

    def make_engine(k=0):
        return ServingEngine(params, cfg, ServingConfig(
            max_slots=max_slots, max_queue=max(n_requests, 1),
            max_seq_len=64, prompt_buckets=(8, 16),
            prefill_chunk_tokens=chunk, prefix_cache_mb=prefix_mb,
            speculative_k=k, kv_cache_dtype=kv_dtype))

    # warmup engine: pays every compile (batched prefill at BOTH buckets
    # + the one decode program) and anchors correctness against one-shot
    # generate(). The warm prompts deliberately share no prefix with each
    # other, so the second one cannot hit the warm engine's prefix cache
    # and shrink its computed suffix out of bucket 16.
    wrng = np.random.RandomState(99)
    short_p = wrng.randint(0, cfg.vocab_size, (8,)).tolist()    # bucket 8
    long_p = wrng.randint(0, cfg.vocab_size, (16,)).tolist()    # bucket 16
    warm = make_engine()
    w0 = warm.submit(short_p, max_new_tokens=max_new)
    warm.drain(max_steps=10 * max_new)
    w1 = warm.submit(long_p, max_new_tokens=max_new)
    warm.drain(max_steps=10 * max_new)
    for fut, p in ((w0, short_p), (w1, long_p)):
        want = np.asarray(generate(
            params, cfg, np.asarray([p], np.int32), max_new))[0].tolist()
        got = fut.result(timeout=5)
        if kv_dtype == "fp32":
            assert got == want, "serving diverged from generate()"
        else:                       # quantized KV: threshold, not bitwise
            match = sum(g == w for g, w in zip(got, want)) / len(want)
            assert match >= 0.9, f"quantized KV parity too low ({match:.2f})"
    if spec_k > 0:                  # pay the speculative-step compile too
        warm_spec = make_engine(spec_k)
        ws = warm_spec.submit(short_p, max_new_tokens=max_new)
        warm_spec.drain(max_steps=10 * max_new)
        ws.result(timeout=5)

    def measure(k):
        eng = make_engine(k)
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        eng.drain(max_steps=100 * max_new * max(1, n_requests // max_slots))
        tokens = sum(len(f.result(timeout=5)) for f in futs)
        return tokens, time.perf_counter() - t0, eng.metrics.snapshot()

    # spec-off leg first: its decode tokens/sec is the comparison anchor
    _, _, snap_off = measure(0)
    if spec_k > 0:
        tokens, wall_s, snap = measure(spec_k)
    else:
        tokens, wall_s, snap = measure(0)

    result = {
        "platform": platform,
        "model": "gpt2-tiny(L4,H128)",
        "requests": n_requests,
        "max_slots": max_slots,
        "max_new_tokens": max_new,
        "prefill_chunk_tokens": chunk,
        "prefix_cache_mb": prefix_mb,
        "speculative_k": spec_k,
        "kv_cache_dtype": kv_dtype,
        "tokens_per_sec": round(tokens / wall_s, 1),
        "decode_tokens_per_sec": round(snap["tokens_per_sec"] or 0.0, 1),
        "decode_tokens_per_sec_spec_off": round(
            snap_off["tokens_per_sec"] or 0.0, 1),
        "accept_rate": (None if snap["accept_rate"] is None
                        else round(snap["accept_rate"], 3)),
        "tokens_per_step": (None if snap["tokens_per_step"] is None
                            else round(snap["tokens_per_step"], 2)),
        "kv_pool_bytes": snap["kv_pool_bytes"],
        "prefill_tokens_per_sec": round(
            snap["prefill_tokens_per_sec"] or 0.0, 1),
        "avg_ttft_s": round(snap["avg_ttft_s"], 4),
        "max_ttft_s": round(snap["max_ttft_s"], 4),
        "ttft_p50_s": round(snap["ttft_p50_s"], 4),
        "ttft_p95_s": round(snap["ttft_p95_s"], 4),
        "prefix_hit_rate": (None if snap["prefix_hit_rate"] is None
                            else round(snap["prefix_hit_rate"], 3)),
        "decode_steps": snap["decode_steps"],
        "complete": True,
    }
    suffix = "" if platform == "tpu" else f"_{platform.upper()}"
    # BENCH_SERVE_OUT redirects the artifact (tools/bench_gate.py runs a
    # fresh bench to a temp path and diffs it against the committed JSON —
    # the committed baseline must not be clobbered by the comparison run)
    out = os.environ.get("BENCH_SERVE_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"SERVING_BENCH{suffix}.json")
    previous = None
    if os.path.exists(out):
        try:
            with open(out) as f:
                previous = json.load(f)
        except (OSError, ValueError):
            previous = None
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    if previous and previous.get("avg_ttft_s"):
        before, after = previous["avg_ttft_s"], result["avg_ttft_s"]
        print(f"# avg TTFT: {before:.4f}s -> {after:.4f}s "
              f"({before / after:.2f}x)" if after else
              f"# avg TTFT: {before:.4f}s -> {after}")
    if previous and previous.get("decode_tokens_per_sec"):
        before = previous["decode_tokens_per_sec"]
        after = result["decode_tokens_per_sec"]
        print(f"# decode tokens/sec: {before:.1f} -> {after:.1f} "
              f"({after / before:.2f}x, speculative_k={spec_k}, "
              f"kv={kv_dtype})")
    if spec_k > 0 and result["decode_tokens_per_sec_spec_off"]:
        off = result["decode_tokens_per_sec_spec_off"]
        on = result["decode_tokens_per_sec"]
        rate = result["accept_rate"]
        print(f"# speculation off->on this run: {off:.1f} -> {on:.1f} "
              f"({on / off:.2f}x, accept_rate="
              f"{rate if rate is None else round(rate, 3)})")

    print(json.dumps({
        "metric": f"continuous-batching serving tokens/sec ({platform})",
        "value": result["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,
        **{k: result[k] for k in ("avg_ttft_s", "ttft_p50_s", "ttft_p95_s",
                                  "max_ttft_s", "requests", "max_slots",
                                  "max_new_tokens", "decode_tokens_per_sec",
                                  "decode_tokens_per_sec_spec_off",
                                  "speculative_k", "kv_cache_dtype",
                                  "accept_rate", "tokens_per_step",
                                  "prefill_tokens_per_sec",
                                  "prefix_hit_rate")},
    }))
    return 0


def mesh_child_main():
    """Mesh-sharded serving leg: tensor-parallel oracle + throughput on a
    virtual multi-device CPU mesh.

    Runs the SAME continuous-batching engine at mesh shapes (1,1), (1,2)
    and (1,4) — params sharded per the registry's Megatron split, the
    paged KV pool sharded over heads on the ``model`` axis — and asserts
    the bitwise continuous-vs-``generate()`` oracle holds SHARDED for
    dense and the pallas decode kernel tier, speculation off and on.
    CPU-emulated SPMD is slower than single-device (GSPMD inserts real
    collectives and the "devices" share one socket), so the artifact
    records tok/s retention vs the (1,1) leg rather than a speedup;
    tools/bench_gate.py refuses a false ``sharded_oracle_ok`` and
    retention collapse. Writes MESH_BENCH_CPU.json (BENCH_MESH_OUT
    redirects). Knobs: BENCH_MESH_REQUESTS / BENCH_MESH_NEW_TOKENS /
    BENCH_MESH_SPEC_K."""
    # the device-virtualization flag must land before jax initializes;
    # bench.py's parent never imports jax, so setting it here works for
    # direct ``BENCH_MODEL=mesh python bench.py --child`` runs too
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    n_requests = int(os.environ.get("BENCH_MESH_REQUESTS", "8"))
    max_new = int(os.environ.get("BENCH_MESH_NEW_TOKENS", "16"))
    spec_k = int(os.environ.get("BENCH_MESH_SPEC_K", "4"))
    shapes = ((1, 1), (1, 2), (1, 4))

    cfg = GPT2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=512,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=1, seq_len=8, seed=0)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).tolist()
               for n in rng.randint(4, 13, size=n_requests)]  # buckets 8/16

    def progress(msg):
        print(f"# {msg}", file=sys.stderr, flush=True)

    # single-device greedy references, one per (prompt, impl): the oracle
    # every sharded engine run must reproduce token-for-token
    refs = {}

    def reference(p, impl):
        key = (tuple(p), impl)
        if key not in refs:
            refs[key] = np.asarray(generate(
                params, cfg, np.asarray([p], np.int32), max_new,
                attn_impl=impl))[0].tolist()
        return refs[key]

    def pool_bytes_per_device(eng):
        dev0 = jax.devices()[0]
        total = 0
        for arr in (eng.pool.k, eng.pool.v):
            total += sum(s.data.nbytes for s in arr.addressable_shards
                         if s.device == dev0)
        return total

    def run_leg(shape, impl, k):
        eng = ServingEngine(params, cfg, ServingConfig(
            max_slots=4, max_queue=n_requests, max_seq_len=64,
            prompt_buckets=(8, 16), speculative_k=k,
            attention_impl={"default": impl}, mesh_shape=shape))
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        eng.drain(max_steps=400 * max_new)
        wall = time.perf_counter() - t0
        outs = [f.result(timeout=5) for f in futs]
        oracle_ok = all(out == reference(p, impl)
                        for out, p in zip(outs, prompts))
        tokens = sum(len(out) for out in outs)
        snap = eng.metrics.snapshot()
        return {
            "mesh_shape": list(shape),
            "attention_impl": impl,
            "speculative_k": k,
            "oracle_ok": oracle_ok,
            "tokens_per_sec": round(tokens / wall, 1),
            "avg_ttft_s": round(snap["avg_ttft_s"], 4),
            "kv_pool_bytes_per_device": pool_bytes_per_device(eng),
        }

    legs = []
    for shape in shapes:
        for impl in ("dense", "pallas_decode"):
            for k in (0, spec_k) if spec_k > 0 else (0,):
                leg = run_leg(shape, impl, k)
                legs.append(leg)
                progress(
                    f"mesh={shape} impl={impl} k={k}: "
                    f"oracle={'OK' if leg['oracle_ok'] else 'MISMATCH'} "
                    f"{leg['tokens_per_sec']:.1f} tok/s "
                    f"ttft={leg['avg_ttft_s']:.4f}s "
                    f"pool/dev={leg['kv_pool_bytes_per_device']}")

    oracle_ok = all(leg["oracle_ok"] for leg in legs)
    assert oracle_ok, "sharded serving diverged from generate()"

    def agg(shape):
        rows = [l for l in legs if tuple(l["mesh_shape"]) == shape]
        return {
            "tokens_per_sec": round(
                sum(l["tokens_per_sec"] for l in rows) / len(rows), 1),
            "avg_ttft_s": round(
                sum(l["avg_ttft_s"] for l in rows) / len(rows), 4),
            "kv_pool_bytes_per_device": rows[0]["kv_pool_bytes_per_device"],
        }

    base = agg((1, 1))
    per_shape = {"x".join(map(str, s)): agg(s) for s in shapes}
    retention = {
        name: round(row["tokens_per_sec"] / base["tokens_per_sec"], 3)
        for name, row in per_shape.items()
    }
    result = {
        "platform": "cpu",
        "model": "gpt2-tiny(L2,H64,heads4)",
        "n_devices": len(jax.devices()),
        "requests": n_requests,
        "max_new_tokens": max_new,
        "speculative_k": spec_k,
        "mesh_shapes": ["x".join(map(str, s)) for s in shapes],
        "sharded_oracle_ok": oracle_ok,
        "per_shape": per_shape,
        "legs": legs,
        "complete": True,
    }
    # flat copies of the gate-worthy numbers: tools/bench_gate.py's
    # compare() reads top-level keys only
    for name, row in per_shape.items():
        result[f"tokens_per_sec_{name}"] = row["tokens_per_sec"]
        result[f"avg_ttft_s_{name}"] = row["avg_ttft_s"]
        result[f"kv_pool_bytes_per_device_{name}"] = \
            row["kv_pool_bytes_per_device"]
        result[f"retention_{name}"] = retention[name]
    out = os.environ.get("BENCH_MESH_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "MESH_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    kv11 = per_shape["1x1"]["kv_pool_bytes_per_device"]
    kv14 = per_shape["1x4"]["kv_pool_bytes_per_device"]
    print(json.dumps({
        "metric": "mesh-sharded serving tok/s retention (1x4 vs 1x1, cpu)",
        "value": retention["1x4"],
        "unit": "x single-device tokens/sec",
        "vs_baseline": None,
        "sharded_oracle_ok": oracle_ok,
        "kv_pool_bytes_per_device_1x1": kv11,
        "kv_pool_bytes_per_device_1x4": kv14,
        "kv_pool_shard_factor": round(kv11 / kv14, 2) if kv14 else None,
        **{f"tokens_per_sec_{n}": r["tokens_per_sec"]
           for n, r in per_shape.items()},
    }))
    return 0


def memtier_child_main():
    """Memory-tier leg: spilled-hit TTFT vs cold re-prefill TTFT.

    A deliberately tiny live prefix cache (holds ONE long-prompt entry)
    plus a generous host-RAM spill tier forces every alternation between
    two long shared prompts through demote->promote: serving prompt A
    evicts B's entry to spill and vice versa, so after the first two
    serves every request is a spilled hit whose computed suffix is a
    single token (bucket 16 prefill) instead of the full 448-token
    bucket. The cold leg serves the same-length but mutually disjoint
    prompts on an identically configured engine, so its TTFT is the
    re-prefill cost the spill tier avoids — decode cost is identical in
    both legs (same decode program, same max_new_tokens), so the TTFT
    ratio isolates the prefill saved. Every output is asserted bitwise
    against one-shot generate() (fp32 KV), and a corruption mini-leg
    flips a byte in a spilled blob and re-serves: the entry must be
    dropped (counted), the request must still complete bitwise via a
    normal prefill, and corrupt_entries_served must stay 0. Writes
    MEMTIER_BENCH[_CPU].json (BENCH_MEMTIER_OUT redirects). Knobs:
    BENCH_MEMTIER_ROUNDS / BENCH_MEMTIER_NEW_TOKENS."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    dev = jax.devices()[0]
    platform = dev.platform
    n_rounds = int(os.environ.get("BENCH_MEMTIER_ROUNDS", "6"))
    max_new = int(os.environ.get("BENCH_MEMTIER_NEW_TOKENS", "16"))

    cfg = GPT2Config(
        vocab_size=512, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=4, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=1, seq_len=8, seed=0)

    prompt_len = 440                    # bucket 448 when prefilled cold
    # one 440-token fp32 entry is ~1.8MB (2 * L4 * hidden128 * 4B/tok);
    # 2.2MB holds exactly one, so the second prompt's insert always
    # demotes the first to spill — the alternation below then promotes
    # on every serve.
    live_mb, spill_mb = 2.2, 32.0

    def make_engine():
        return ServingEngine(params, cfg, ServingConfig(
            max_slots=2, max_queue=8, max_seq_len=512,
            prompt_buckets=(16, 448), prefix_cache_mb=live_mb,
            prefix_spill_mb=spill_mb))

    rng = np.random.RandomState(0)
    prompt_a = rng.randint(0, cfg.vocab_size, (prompt_len,)).tolist()
    prompt_b = rng.randint(0, cfg.vocab_size, (prompt_len,)).tolist()
    cold_prompts = [rng.randint(0, cfg.vocab_size, (prompt_len,)).tolist()
                    for _ in range(n_rounds)]

    def serve_timed(eng, prompt):
        """One request at a time: returns (output_tokens, its TTFT)."""
        fut = eng.submit(prompt, max_new_tokens=max_new)
        eng.drain(max_steps=50 * max_new)
        out = fut.result(timeout=10)
        return out, eng.metrics._ttft_window[-1]

    # warm engine: pays every compile (bucket 448 + bucket 16 prefill +
    # the decode program — shared process-wide) and anchors correctness
    # against one-shot generate() at both bucket shapes.
    warm = make_engine()
    short_p = rng.randint(0, cfg.vocab_size, (12,)).tolist()    # bucket 16
    for p in (prompt_a, short_p):
        out, _ = serve_timed(warm, p)
        want = np.asarray(generate(
            params, cfg, np.asarray([p], np.int32), max_new))[0].tolist()
        assert out == want, "memtier warmup diverged from generate()"

    want_a = np.asarray(generate(
        params, cfg, np.asarray([prompt_a], np.int32),
        max_new))[0].tolist()
    want_b = np.asarray(generate(
        params, cfg, np.asarray([prompt_b], np.int32),
        max_new))[0].tolist()

    # cold leg: disjoint prompts -> every serve is a full 448-bucket
    # re-prefill (the engine config is identical, so the only variable
    # vs the spill leg is where the prefix KV comes from)
    cold_eng = make_engine()
    cold_ttfts = []
    for p in cold_prompts:
        _, ttft = serve_timed(cold_eng, p)
        cold_ttfts.append(ttft)
    cold_snap = cold_eng.metrics.snapshot()

    # spill leg: A and B alternate through the one-entry live tier, so
    # every serve after the first two promotes its prefix from spill
    # and prefills a single-token suffix
    eng = make_engine()
    oracle_ok = True
    out, _ = serve_timed(eng, prompt_a)             # cold: inserts A
    oracle_ok &= out == want_a
    out, _ = serve_timed(eng, prompt_b)             # inserts B, spills A
    oracle_ok &= out == want_b
    spill_ttfts = []
    for _ in range(n_rounds):
        for prompt, want in ((prompt_a, want_a), (prompt_b, want_b)):
            out, ttft = serve_timed(eng, prompt)
            spill_ttfts.append(ttft)
            oracle_ok &= out == want
    stats = eng.prefix_cache.stats()
    spill_snap = eng.metrics.snapshot()

    # corruption mini-leg: flip a byte in a spilled blob, then serve the
    # matching prompt — the store must drop the corrupt entry (counted)
    # and the request must still complete bitwise via a normal prefill
    corrupt_before = eng.prefix_cache.spill.stats()["corrupt_dropped"]
    assert eng.prefix_cache.corrupt_spilled(), "nothing spilled to corrupt"
    spilled_key = next(iter(eng.prefix_cache.spill._records))
    victim = list(spilled_key[1:])
    want_v = want_a if victim == prompt_a else want_b
    out, _ = serve_timed(eng, victim)
    corrupt_dropped = (eng.prefix_cache.spill.stats()["corrupt_dropped"]
                       - corrupt_before)
    corrupt_entries_served = 0 if out == want_v else 1
    spill_integrity_ok = bool(corrupt_dropped >= 1
                              and corrupt_entries_served == 0)

    cold_ttft = sum(cold_ttfts) / len(cold_ttfts)
    spilled_ttft = sum(spill_ttfts) / len(spill_ttfts)
    result = {
        "platform": platform,
        "model": "gpt2-tiny(L4,H128)",
        "rounds": n_rounds,
        "max_new_tokens": max_new,
        "prompt_len": prompt_len,
        "prefix_cache_mb": live_mb,
        "prefix_spill_mb": spill_mb,
        "cold_ttft_s": round(cold_ttft, 4),
        "spilled_hit_ttft_s": round(spilled_ttft, 4),
        "ttft_improvement": round(cold_ttft / spilled_ttft, 2),
        "decode_tokens_per_sec_cold": round(
            cold_snap["tokens_per_sec"] or 0.0, 1),
        "decode_tokens_per_sec": round(
            spill_snap["tokens_per_sec"] or 0.0, 1),
        "spill_hits": stats["spill_hits"],
        "spill_promotions": stats["spill_promotions"],
        "spill_demotions": stats["spill"]["demotions"],
        "spill_hit_rate": (None if stats["spill_hit_rate"] is None
                           else round(stats["spill_hit_rate"], 3)),
        "spill_corrupt_dropped": corrupt_dropped,
        "corrupt_entries_served": corrupt_entries_served,
        "oracle_ok": bool(oracle_ok),
        "spill_integrity_ok": spill_integrity_ok,
        "complete": True,
    }
    suffix = "" if platform == "tpu" else f"_{platform.upper()}"
    # BENCH_MEMTIER_OUT redirects the artifact (tools/bench_gate.py runs
    # a fresh bench to a temp path and diffs against the committed JSON)
    out_path = os.environ.get("BENCH_MEMTIER_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"MEMTIER_BENCH{suffix}.json")
    previous = None
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                previous = json.load(f)
        except (OSError, ValueError):
            previous = None
    with open(out_path, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    if previous and previous.get("ttft_improvement"):
        print(f"# spilled-hit TTFT advantage: "
              f"{previous['ttft_improvement']:.2f}x -> "
              f"{result['ttft_improvement']:.2f}x")
    print(f"# cold re-prefill TTFT {cold_ttft:.4f}s vs spilled-hit TTFT "
          f"{spilled_ttft:.4f}s ({result['ttft_improvement']:.2f}x); "
          f"{stats['spill_hits']} spilled hits, "
          f"{corrupt_dropped} corrupt entries dropped, "
          f"{corrupt_entries_served} served")

    print(json.dumps({
        "metric": f"prefix-KV spill tier TTFT advantage ({platform})",
        "value": result["ttft_improvement"],
        "unit": "x cold re-prefill TTFT",
        "vs_baseline": None,
        **{k: result[k] for k in ("cold_ttft_s", "spilled_hit_ttft_s",
                                  "spill_hits", "spill_promotions",
                                  "spill_demotions", "spill_hit_rate",
                                  "decode_tokens_per_sec",
                                  "decode_tokens_per_sec_cold",
                                  "spill_corrupt_dropped",
                                  "corrupt_entries_served",
                                  "oracle_ok", "spill_integrity_ok")},
    }))
    return 0


def longdoc_child_main():
    """Long-document serving leg: paged KV pool + per-bucket attention
    backends at the 16k prompt bucket.

    Serves the same workload twice — the 16384 bucket on the dense
    backend, then on ``sparse_xla`` (short/mid buckets stay dense in
    both legs, as a real ladder would run them) — and reports per
    backend: 16k-bucket-only end-to-end tokens/sec (phase A, the
    speedup attribution number), mixed-traffic tokens/sec with two
    shared-prefix 16k documents alongside short chat requests (phase
    B), and TTFT stats. The paged pool runs at a ~28% budget of the
    contiguous ``MaxSlots x S_max`` footprint, which the artifact
    records (``pool_vs_contiguous``) — the 16k ladder is only servable
    BECAUSE of paging. Output parity is asserted in-run: every dense
    lane bitwise vs dense ``generate()`` (the 16k dense lanes are
    pinned through the same program at the 2048 bucket — a one-shot
    dense 16k reference would materialize a [1, nh, 16k, 16k] score
    tensor), sparse 16k lanes bitwise vs sparse ``generate()``.
    Writes LONGDOC_BENCH[_CPU].json (BENCH_LONGDOC_OUT redirects, as
    the bench gate does). Knobs: BENCH_LONGDOC_NEW (new tokens per
    16k document, default 32)."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    def progress(msg):
        print(f"# longdoc: {msg}", file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    platform = dev.platform
    new_long = int(os.environ.get("BENCH_LONGDOC_NEW", "32"))
    new_short = 24
    page_tokens = 128
    max_seq_len = 16640            # 130 pages: 16384 prompt + headroom
    pool_tokens = 37376            # 292 pages, ~28% of 8 x 16640 contiguous
    buckets = (128, 2048, 16384)

    cfg = GPT2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=max_seq_len,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=1, seq_len=8, seed=0)

    rng = np.random.RandomState(0)
    shared = rng.randint(0, cfg.vocab_size, (8192,)).tolist()
    longdocs = [shared + rng.randint(0, cfg.vocab_size, (8192,)).tolist()
                for _ in range(2)]
    middoc = rng.randint(0, cfg.vocab_size, (1800,)).tolist()
    chats = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
             for n in (16, 33, 64, 100)]

    def make_engine(impl):
        return ServingEngine(params, cfg, ServingConfig(
            max_slots=8, max_queue=16, max_seq_len=max_seq_len,
            prompt_buckets=buckets, prefill_chunk_tokens=2048,
            kv_page_tokens=page_tokens, kv_pool_tokens=pool_tokens,
            attention_impl={"default": "dense", 16384: impl}))

    def serve(eng, jobs):
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
        eng.drain(max_steps=200000)
        outs = [f.result(timeout=60) for f in futs]
        return outs, time.perf_counter() - t0, eng.metrics.snapshot()

    def oneshot(prompt, n_new, impl):
        out = generate(params, cfg, np.asarray([prompt], np.int32), n_new,
                       attn_impl=impl, kv_page_tokens=page_tokens)
        return np.asarray(out)[0].tolist()

    # references (short/mid lanes run dense under BOTH legs)
    progress("building generate() references")
    want_mid = oneshot(middoc, new_short, "dense")
    want_chats = [oneshot(c, new_short, "dense") for c in chats]
    want_long_sparse = oneshot(longdocs[0], new_long, "sparse_xla")

    flat = {}
    pool_bytes = contiguous = None
    for impl in ("dense", "sparse_xla"):
        # warmup engine: pay every compile for this leg (prefill at each
        # bucket + both decode program classes) before the clock starts;
        # one concurrent drain so warmup wall ~= the slowest document
        progress(f"{impl}: warmup (all buckets, one concurrent serve)")
        warm = make_engine(impl)
        serve(warm, [(chats[0], new_short), (middoc, new_short),
                     (longdocs[0], new_long)])
        pool_bytes = warm.pool.nbytes()
        contiguous = warm.pool.contiguous_equiv_bytes()
        del warm

        # phase A: the 16k bucket alone — the speedup attribution number
        progress(f"{impl}: phase A (2 x 16k documents)")
        outs_a, wall_a, _ = serve(make_engine(impl),
                                  [(p, new_long) for p in longdocs])
        tokens_a = sum(len(o) for o in outs_a)

        # phase B: shared-prefix 16k documents mixed with chat traffic
        progress(f"{impl}: phase B (mixed 16k + chat traffic)")
        jobs = ([(p, new_long) for p in longdocs] + [(middoc, new_short)]
                + [(c, new_short) for c in chats])
        outs_b, wall_b, snap = serve(make_engine(impl), jobs)
        tokens_b = sum(len(o) for o in outs_b)
        progress(f"{impl}: phase A {wall_a:.1f}s, phase B {wall_b:.1f}s")

        oracle_ok = (outs_b[2] == want_mid
                     and all(o == w for o, w in zip(outs_b[3:], want_chats)))
        if impl == "sparse_xla":
            oracle_ok = (oracle_ok and outs_a[0] == want_long_sparse
                         and outs_b[0] == want_long_sparse)
        assert oracle_ok, f"{impl}: serving diverged from generate()"
        key = "sparse" if impl == "sparse_xla" else impl
        flat.update({
            f"{key}_longdoc_tokens_per_sec": round(tokens_a / wall_a, 2),
            f"{key}_mixed_tokens_per_sec": round(tokens_b / wall_b, 2),
            f"{key}_avg_ttft_s": round(snap["avg_ttft_s"], 4),
            f"{key}_ttft_p50_s": round(snap["ttft_p50_s"], 4),
            f"{key}_ttft_p95_s": round(snap["ttft_p95_s"], 4),
            f"{key}_oracle_ok": bool(oracle_ok),
        })

    speedup = (flat["sparse_longdoc_tokens_per_sec"]
               / flat["dense_longdoc_tokens_per_sec"])
    result = {
        "platform": platform,
        "model": "gpt2-tiny(L2,H64)",
        "max_slots": 8,
        "page_tokens": page_tokens,
        "kv_pool_tokens": pool_tokens,
        "prompt_buckets": list(buckets),
        "longdoc_prompt_len": len(longdocs[0]),
        "longdoc_new_tokens": new_long,
        "shared_prefix_len": len(shared),
        "requests_mixed": 2 + 1 + len(chats),
        **flat,
        "speedup_sparse_vs_dense_16k": round(speedup, 2),
        "pool_bytes": pool_bytes,
        "contiguous_equiv_bytes": contiguous,
        "pool_vs_contiguous": round(pool_bytes / contiguous, 3),
        "complete": True,
    }
    suffix = "" if platform == "tpu" else f"_{platform.upper()}"
    out = os.environ.get("BENCH_LONGDOC_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"LONGDOC_BENCH{suffix}.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": f"16k-bucket sparse-vs-dense serving speedup ({platform})",
        "value": result["speedup_sparse_vs_dense_16k"],
        "unit": "x dense end-to-end tokens/sec",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "dense_longdoc_tokens_per_sec", "sparse_longdoc_tokens_per_sec",
            "dense_mixed_tokens_per_sec", "sparse_mixed_tokens_per_sec",
            "dense_avg_ttft_s", "sparse_avg_ttft_s", "pool_vs_contiguous")},
    }))
    return 0


def kernels_child_main():
    """Kernel-tier microbench: per-kernel wall time, Pallas vs the
    composed-XLA fallback, with the parity oracle asserted in-run.

    Times `decode_attend` (fp32 paged + int8 fused-dequant) and
    `band_attend` through both impls at one fixed shape each. On CPU
    the Pallas numbers run in INTERPRET mode — they are a correctness
    treadmill and a relative-regression tripwire for the fallback path,
    not kernel perf (the artifact records ``interpret`` so the gate
    never compares across modes); on a real TPU the same leg times the
    native kernels. Every timed sample is checked against the other
    impl (`*_parity_ok`) — a kernel that drifts from its oracle must
    fail the bench, not ship a number. Writes KERNEL_BENCH[_CPU].json
    (BENCH_KERNELS_OUT redirects, as the bench gate does). Knobs:
    BENCH_KERNELS_ITERS (timed iterations per impl, default 10)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu import kernels

    def progress(msg):
        print(f"# kernels: {msg}", file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    platform = dev.platform
    interpret = jax.default_backend() != "tpu"
    iters = int(os.environ.get("BENCH_KERNELS_ITERS", "10"))

    # decode shape: one serving-like decode step (C=1) over a paged pool
    B, C, nh, pt, hd, mp = 4, 1, 4, 16, 64, 4
    P = B * mp + 1
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, C, nh, hd), jnp.float32)
    pk = jnp.asarray(rng.randn(P, nh, pt, hd), jnp.float32)
    pv = jnp.asarray(rng.randn(P, nh, pt, hd), jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(B * mp).reshape(B, mp), jnp.int32)
    qpos = jnp.asarray(
        np.full((B, C), mp * pt - 3), jnp.int32)
    sk = jnp.asarray(np.abs(rng.randn(P, nh)) / 127.0 + 1e-6, jnp.float32)
    sv = jnp.asarray(np.abs(rng.randn(P, nh)) / 127.0 + 1e-6, jnp.float32)
    pk8 = jnp.asarray(rng.randint(-127, 128, (P, nh, pt, hd)), jnp.int8)
    pv8 = jnp.asarray(rng.randint(-127, 128, (P, nh, pt, hd)), jnp.int8)

    # band shape: one window-backend decode step, flattened queries
    N, W = 8, 3 * pt
    bq = jnp.asarray(rng.randn(N, nh, hd), jnp.float32)
    bkw = jnp.asarray(rng.randn(N, nh, W, hd), jnp.float32)
    bvw = jnp.asarray(rng.randn(N, nh, W, hd), jnp.float32)
    bks = jnp.asarray(rng.randn(N, nh, pt, hd), jnp.float32)
    bvs = jnp.asarray(rng.randn(N, nh, pt, hd), jnp.float32)
    base = jnp.asarray(np.full(N, 2 * pt), jnp.int32)
    pos = base + jnp.asarray(np.arange(N) + 4, jnp.int32)

    # every operand is a jit ARGUMENT (a nullary closure would let XLA
    # constant-fold the whole attention into a baked buffer)
    def decode_case(impl, scaled):
        def f(q_, k_, v_, t_, p_, *scales):
            kw = (dict(k_scale=scales[0], v_scale=scales[1])
                  if scales else {})
            return kernels.decode_attend(
                q_, k_, v_, t_, p_, page_tokens=pt, dtype=jnp.float32,
                impl=impl, interpret=interpret, **kw)
        args = ((q, pk8, pv8, tables, qpos, sk, sv) if scaled
                else (q, pk, pv, tables, qpos))
        return f, args

    def band_case(impl, _scaled):
        def f(q_, kw_, vw_, ks_, vs_, pos_, base_):
            return kernels.band_attend(
                q_, kw_, vw_, ks_, vs_, pos_, base_, dtype=jnp.float32,
                impl=impl, interpret=interpret)
        return f, (bq, bkw, bvw, bks, bvs, pos, base)

    cases = {"decode": (decode_case, False),
             "decode_int8": (decode_case, True),
             "band": (band_case, False)}

    flat = {}
    for name, (make, scaled) in cases.items():
        outs = {}
        for impl in ("pallas", "xla"):
            progress(f"{name}/{impl}: warmup + {iters} timed iterations")
            f, args = make(impl, scaled)
            run = jax.jit(f)
            run(*args).block_until_ready()         # compile outside clock
            t0 = time.perf_counter()
            for _ in range(iters):
                out = run(*args)
            out.block_until_ready()
            dt = time.perf_counter() - t0
            outs[impl] = np.asarray(out)
            flat[f"{name}_{impl}_us"] = round(dt / iters * 1e6, 1)
        parity = bool(np.allclose(outs["pallas"], outs["xla"],
                                  rtol=1e-5, atol=1e-5))
        flat[f"{name}_parity_ok"] = parity
        assert parity, f"{name}: pallas diverged from the XLA fallback"

    result = {
        "platform": platform,
        "interpret": interpret,
        "iters": iters,
        "decode_shape": [B, C, nh, pt, hd, mp],
        "band_shape": [N, nh, W, pt, hd],
        **flat,
        "complete": True,
    }
    suffix = "" if platform == "tpu" else f"_{platform.upper()}"
    out_path = os.environ.get("BENCH_KERNELS_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        f"KERNEL_BENCH{suffix}.json")
    with open(out_path, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": f"kernel-tier microbench ({platform}"
                  f"{', interpret' if interpret else ''})",
        "value": result["decode_pallas_us"],
        "unit": "us/call fused paged decode",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "decode_xla_us", "decode_int8_pallas_us", "decode_int8_xla_us",
            "band_pallas_us", "band_xla_us", "decode_parity_ok",
            "decode_int8_parity_ok", "band_parity_ok")},
    }))
    return 0


def fleet_child_main():
    """Fleet serving leg: replica scale-out throughput + kill recovery.

    Spawns 1 -> 2 -> 4 REAL replica processes (``python -m
    deepspeed_tpu.inference.serving.replica``, each its own jax runtime
    pinned to the CPU backend) and drives the same request mix through
    the stdlib Router — this parent never imports jax. Reports
    aggregate streamed tokens/sec per fleet size and the 2x/4x scaling
    factors, then a final 2-replica leg that arms ``kill_replica``
    mid-decode and measures the wall time from replica death to the
    last re-routed request completing (``kill_recovery_s``), asserting
    zero poisoned requests and bitwise-identical outputs across every
    fleet size (the failover oracle, greedy determinism).

    Core-starved machines (this CI box has ONE core) cap wall-clock
    scaling at ~1.0x no matter how good the router is, so the leg
    records BOTH wall-clock and CPU-time-normalized throughput — each
    replica's socket health op reports ``process_cpu_s`` and
    ``tokens_total``, and the per-replica rates ``tokens_r / cpu_r``
    sum to the aggregate the fleet would sustain with a core per
    replica. ``scaling_mode`` ("wall" when the box has at least as many
    cores as the largest fleet, else "cpu") selects which series feeds
    the headline ``fleet_tokens_per_sec_N`` / ``fleet_scaling_*`` keys
    the bench gate compares; artifacts from different modes are never
    comparable. Writes FLEET_BENCH_CPU.json (BENCH_FLEET_OUT redirects,
    as the gate does). Knobs: BENCH_FLEET_REQUESTS (default 32),
    BENCH_FLEET_NEW_TOKENS (default 32)."""
    import shutil
    import socket
    import tempfile

    from deepspeed_tpu.inference.serving.config import FleetConfig
    from deepspeed_tpu.inference.serving.router import (
        ReplicaEndpoint, Router, read_line, send_line)

    def progress(msg):
        print(f"# fleet: {msg}", file=sys.stderr, flush=True)

    # model sizing matters on a core-starved box: with a dispatch-
    # dominated tiny model (~1ms/step) the solo leg runs cache-warm
    # while multi-replica legs pay a cache refill on every context
    # switch, inflating per-token CPU ~30% and corrupting the scaling
    # ratio. At hidden 128 x 4 layers, per-step compute amortizes the
    # switch penalty and per-replica efficiency is fleet-size-invariant.
    model = {"vocab_size": 101, "hidden_size": 128, "num_hidden_layers": 4,
             "num_attention_heads": 4, "max_position_embeddings": 128}
    # keep requests a multiple of max_slots x max(counts): every fleet
    # size then runs full 4-lane waves, so per-token step cost is
    # occupancy-invariant and the scaling ratio measures the fleet,
    # not batch-fill accidents
    n_requests = int(os.environ.get("BENCH_FLEET_REQUESTS", "32"))
    n_new = int(os.environ.get("BENCH_FLEET_NEW_TOKENS", "32"))
    counts = (1, 2, 4)
    cores = os.cpu_count() or 1
    mode = "wall" if cores >= max(counts) else "cpu"
    prompts = [[(7 * i + 3 * j + 1) % model["vocab_size"] for j in range(8)]
               for i in range(n_requests)]
    tmp = tempfile.mkdtemp(prefix="fleet_bench_")

    def spawn(name, faults=None):
        spec = {"model": model, "seed": 0, "ds_config": {
            "train_batch_size": 1,
            "serving": {"max_slots": 4, "max_queue": 256, "max_seq_len": 128,
                        **({"fault_injection": faults} if faults else {})}}}
        path = os.path.join(tmp, f"{name}.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        def _favor_decode():
            # priority-isolate the data plane: the router (this bench
            # process) wakes on every streamed token frame, and on a
            # core-starved box those wakeups preempt OTHER replicas
            # mid-decode-step — a disturbance that grows with fleet
            # size and pollutes per-replica CPU. Nicing replicas above
            # the front-door keeps decode steps intact; unprivileged
            # boxes skip it (the scheduler bias is an optimization,
            # not a correctness requirement).
            try:
                os.nice(-5)
            except OSError:
                pass

        proc = subprocess.Popen(
            [sys.executable, "-m", "deepspeed_tpu.inference.serving.replica",
             "--config", path, "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True, preexec_fn=_favor_decode,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
        line = proc.stdout.readline()       # blocks until "ready"
        if not line:
            proc.kill()
            raise RuntimeError(f"replica {name} died before ready")
        ready = json.loads(line)
        assert ready.get("ready"), ready
        return proc, int(ready["port"])

    def health(port):
        with socket.create_connection(("127.0.0.1", port), timeout=10.0) as s:
            s.settimeout(10.0)
            send_line(s, {"op": "health"})
            return read_line(s.makefile("rb")) or {}

    def warm(port, tag):
        # rehearse the measured leg's exact shapes — four concurrent
        # lanes, len-8 prompts, full n_new decode — so every jax
        # compile and first-touch cost lands before any clock or
        # cpu-counter starts. Per-replica shares shrink as the fleet
        # grows (1024 -> 256 tokens at 4 replicas), so any fixed
        # per-replica cost left inside the window would bias the
        # scaling ratio against the larger fleets.
        socks = []
        for k in range(4):
            s = socket.create_connection(("127.0.0.1", port), timeout=600.0)
            s.settimeout(600.0)
            send_line(s, {"op": "submit", "v": 1, "key": f"warm-{tag}-{k}",
                          "prompt": [2, 3, 5, 7, 11, 13, 17, 19],
                          "max_new_tokens": n_new, "eos_token_id": None,
                          "timeout_s": 600.0, "from": 0, "age_s": 0.0})
            socks.append(s)
        for s in socks:
            stream = s.makefile("rb")
            while True:
                doc = read_line(stream)
                if doc is None or "t" not in doc:
                    assert doc and doc.get("done"), f"warmup failed: {doc}"
                    break
            s.close()

    def fleet_router(eps):
        return Router(eps, FleetConfig(
            enabled=True, retry_budget=3, retry_backoff_s=0.05,
            attempt_timeout_s=600.0, health_ttl_s=0.1,
            saturation_queue_depth=256,
            affinity_prefix_tokens=0))      # least-loaded spreads the mix

    def run_fleet(n):
        progress(f"{n} replica(s): spawn + warmup (compile)")
        procs, eps = [], []
        try:
            for i in range(n):
                proc, port = spawn(f"n{n}r{i}")
                procs.append(proc)
                eps.append(ReplicaEndpoint(f"n{n}r{i}", "127.0.0.1", port))
            for i, ep in enumerate(eps):
                warm(ep.port, f"{n}-{i}")
            router = fleet_router(eps)
            h0 = [health(ep.port) for ep in eps]
            t0 = time.perf_counter()
            futs = [router.submit(p, max_new_tokens=n_new, timeout_s=600.0)
                    for p in prompts]
            outs = [f.result(timeout=600) for f in futs]
            wall = time.perf_counter() - t0
            h1 = [health(ep.port) for ep in eps]
            c = router.counters()
            router.close()
            assert c["completed"] == n_requests and c["poisoned"] == 0, c
            toks = [h1[i].get("tokens_total", 0) - h0[i].get("tokens_total", 0)
                    for i in range(n)]
            cpus = [h1[i].get("process_cpu_s", 0.0)
                    - h0[i].get("process_cpu_s", 0.0) for i in range(n)]
            cpu_rate = sum(t / max(s, 1e-9)
                           for t, s in zip(toks, cpus) if t > 0)
            progress(f"{n} replica(s): {sum(toks)} tokens in {wall:.1f}s wall"
                     f" (per-replica shares {toks})")
            return outs, sum(toks) / wall, cpu_rate
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)

    flat = {}
    ref_outs = None
    oracle_ok = True
    try:
        for n in counts:
            outs, wall_rate, cpu_rate = run_fleet(n)
            if ref_outs is None:
                ref_outs = outs
            oracle_ok = oracle_ok and outs == ref_outs
            flat[f"wall_tokens_per_sec_{n}"] = round(wall_rate, 2)
            flat[f"cpu_tokens_per_sec_{n}"] = round(cpu_rate, 2)
            flat[f"fleet_tokens_per_sec_{n}"] = flat[
                f"{mode}_tokens_per_sec_{n}"]

        # kill-recovery: a doomed replica SIGKILLs itself mid-decode
        # (fault_injection kill_replica, busy step 3); every accepted
        # request must still complete on the survivor, bitwise
        progress("kill-recovery: 2 replicas, one armed to die mid-decode")
        procs = []
        try:
            doomed, p0 = spawn("kr-doomed",
                               faults={"kill_replica": {"at_step": 3}})
            safe, p1 = spawn("kr-safe")
            procs = [doomed, safe]
            warm(p1, "kr")      # survivor warm; warming the doomed one
            #                     would fire its arm before the clock
            router = fleet_router(
                [ReplicaEndpoint("kr-doomed", "127.0.0.1", p0),
                 ReplicaEndpoint("kr-safe", "127.0.0.1", p1)])
            futs = [router.submit(p, max_new_tokens=n_new, timeout_s=600.0)
                    for p in prompts[:6]]
            assert doomed.wait(timeout=600) is not None
            t_kill = time.perf_counter()
            outs = [f.result(timeout=600) for f in futs]
            recovery = time.perf_counter() - t_kill
            c = router.counters()
            router.close()
            assert c["completed"] == 6 and c["poisoned"] == 0, c
            assert c["retried"] >= 1, c     # the death was actually routed
            oracle_ok = oracle_ok and outs == ref_outs[:6]
            progress(f"kill-recovery: {recovery:.2f}s, counters {c}")
            flat.update({"kill_recovery_s": round(recovery, 2),
                         "kill_requests": 6,
                         "kill_retried": c["retried"],
                         "kill_poisoned": c["poisoned"]})
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=30)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    assert oracle_ok, "fleet outputs diverged across replica counts"

    tps = {n: flat[f"fleet_tokens_per_sec_{n}"] for n in counts}
    result = {
        "platform": "cpu",      # replicas are pinned to the CPU backend
        "model": "gpt2-tiny(L4,H128)",
        "requests": n_requests,
        "max_new_tokens": n_new,
        "replica_counts": list(counts),
        "host_cores": cores,
        "scaling_mode": mode,
        **flat,
        "fleet_scaling_2x": round(tps[2] / tps[1], 3),
        "fleet_scaling_4x": round(tps[4] / tps[1], 3),
        "fleet_oracle_ok": bool(oracle_ok),
        "complete": True,
    }
    out = os.environ.get("BENCH_FLEET_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "FLEET_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": "fleet serving scale-out (2 replicas vs 1, "
                  f"{mode}-normalized)",
        "value": result["fleet_scaling_2x"],
        "unit": "x single-replica tokens/sec",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "fleet_tokens_per_sec_1", "fleet_tokens_per_sec_2",
            "fleet_tokens_per_sec_4", "fleet_scaling_4x",
            "kill_recovery_s", "scaling_mode")},
    }))
    return 0


def chaos_child_main():
    """Chaos-harness leg: a seeded randomized fault schedule against a
    live 2-replica fleet, with the self-healing invariants recorded as
    gate-refusable flags.

    Spawns REAL replica processes (chaos-flagged so the socket ``inject``
    op can arm fault points at runtime) behind the stdlib Router, then
    runs ``ChaosHarness.run(BENCH_CHAOS_EPISODES)`` composing
    kill/drain/slow/reject/overload episodes from ``BENCH_CHAOS_SEED``.
    Every completed request is checked bitwise against an in-process
    single-engine ``generate()`` oracle (memoized per prompt). Writes
    CHAOS_BENCH_CPU.json (BENCH_CHAOS_OUT redirects, as the gate does):
    recovery p50/p95 plus four ``invariant_*`` flags the bench gate's
    schema check REFUSES when false — a baseline with a failed invariant
    can never be committed. Recovery times themselves are context-only
    (CPU-noisy), not compared."""
    import shutil
    import tempfile

    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving.autoscaler import (
        ProcessReplicaSpawner,
    )
    from deepspeed_tpu.inference.serving.chaos import ChaosHarness
    from deepspeed_tpu.inference.serving.config import FleetConfig
    from deepspeed_tpu.inference.serving.router import Router
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    def progress(msg):
        print(f"# chaos: {msg}", file=sys.stderr, flush=True)

    model = {"vocab_size": 101, "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2, "max_position_embeddings": 128}
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "0"))
    episodes = int(os.environ.get("BENCH_CHAOS_EPISODES", "20"))
    n_new = int(os.environ.get("BENCH_CHAOS_NEW_TOKENS", "8"))

    gcfg = GPT2Config(**model, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(gcfg, batch_size=1, seq_len=8, seed=0)
    _oracle_cache = {}

    def reference(prompt, max_new):
        key = (tuple(prompt), max_new)
        if key not in _oracle_cache:
            _oracle_cache[key] = np.asarray(generate(
                params, gcfg, np.asarray([prompt], np.int32),
                max_new))[0].tolist()
        return _oracle_cache[key]

    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    cfg_path = os.path.join(tmp, "replica.json")
    with open(cfg_path, "w") as f:
        json.dump({"model": model, "seed": 0, "chaos": True,
                   "ds_config": {"train_batch_size": 1,
                                 "serving": {"max_slots": 4, "max_queue": 16,
                                             "max_seq_len": 128}}}, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    spawner = ProcessReplicaSpawner(cfg_path, env=env)
    router = None
    t_wall = time.perf_counter()
    try:
        progress("spawning 2 chaos-flagged replicas (compile)")
        replicas = [spawner.spawn("c0"), spawner.spawn("c1")]
        router = Router(
            [h.endpoint() for h in replicas],
            FleetConfig(enabled=True, retry_budget=3, retry_backoff_s=0.05,
                        attempt_timeout_s=300.0, health_ttl_s=0.1,
                        saturation_queue_depth=8, shed_retry_after_s=0.1,
                        affinity_prefix_tokens=0))
        # warm both replicas so compiles land before any recovery clock
        for h in replicas:
            router.submit([2, 3, 5, 7], max_new_tokens=n_new).result(
                timeout=600)
        harness = ChaosHarness(
            router, spawner, reference, replicas, seed=seed,
            max_new_tokens=n_new, request_timeout_s=300.0,
            recovery_timeout_s=300.0, vocab=model["vocab_size"])
        progress(f"running {episodes} episodes (seed {seed})")
        report = harness.run(episodes=episodes)
        for i, ep in enumerate(report["episodes"]):
            progress(f"episode {i}: {ep['kind']} completed={ep['completed']}"
                     f" recovery={ep.get('recovery_s', -1):.2f}s")
    finally:
        if router is not None:
            router.close()
        spawner.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)

    result = {
        "platform": "cpu",
        "model": "gpt2-tiny(L2,H32)",
        "chaos_episodes": report["chaos_episodes"],
        "chaos_seed": report["chaos_seed"],
        "faults_composed": ["kill_replica", "drain_replica", "slow_replica",
                            "reject_admission", "overload"],
        "completed_total": report["completed_total"],
        "shed_total": report["shed_total"],
        "errors_total": report["errors_total"],
        "recovery_p50_s": report["recovery_p50_s"],
        "recovery_p95_s": report["recovery_p95_s"],
        "recovery_max_s": report["recovery_max_s"],
        "invariant_bitwise_ok": report["invariant_bitwise_ok"],
        "invariant_no_stuck": report["invariant_no_stuck"],
        "invariant_recovery_bounded": report["invariant_recovery_bounded"],
        "invariant_converged": report["invariant_converged"],
        "wall_s": round(time.perf_counter() - t_wall, 1),
        "complete": True,
    }
    out = os.environ.get("BENCH_CHAOS_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "CHAOS_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": f"chaos schedule ({episodes} episodes, seed {seed}) "
                  "recovery p95",
        "value": result["recovery_p95_s"],
        "unit": "s",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "recovery_p50_s", "completed_total", "shed_total",
            "invariant_bitwise_ok", "invariant_no_stuck",
            "invariant_recovery_bounded", "invariant_converged")},
    }))
    if not (result["invariant_bitwise_ok"] and result["invariant_no_stuck"]
            and result["invariant_recovery_bounded"]
            and result["invariant_converged"]):
        return 1
    return 0


def rollout_child_main():
    """Zero-downtime weight-rollout leg: a live checkpoint hot-swap with
    canary, shadow traffic, and a forced-regression rollback, proven
    exactly-once end to end.

    Spawns 2 incumbent replicas on a committed weight tag, then drives
    :class:`RolloutController` through both halves of its contract under
    continuous traffic:

    1. ROLL-FORWARD: commit a tag with IDENTICAL weights (same init
       seed). The canary's shadow replays diff bitwise-clean, the canary
       slice carries real traffic, and the controller promotes +
       commits, draining the old generation down the SIGTERM path.
    2. FORCED REGRESSION: commit a tag with DIFFERENT weights (new init
       seed). Shadow replays diff, the controller rolls the canary back
       down the same drain path, and the fleet settles on the prior
       generation within ``recovery_bound_s``.

    Every request streams through a ``stream_cb`` idempotency oracle:
    the streamed tokens must equal the final result exactly (no drop, no
    dup, no reorder) and the result must match ONE per-generation
    in-process ``generate()`` reference bitwise — a cross-generation
    splice matches neither. Writes ROLLOUT_BENCH_CPU.json
    (BENCH_ROLLOUT_OUT redirects); the gate's schema check REFUSES any
    dropped/duplicated request, an unbounded rollback, or a canary that
    never carried traffic."""
    import random
    import shutil
    import tempfile

    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving.autoscaler import (
        ProcessReplicaSpawner,
    )
    from deepspeed_tpu.inference.serving.config import (
        FleetConfig,
        RolloutConfig,
    )
    from deepspeed_tpu.inference.serving.rollout import RolloutController
    from deepspeed_tpu.inference.serving.router import Router
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
    from deepspeed_tpu.runtime.checkpoint import CheckpointStorage

    def progress(msg):
        print(f"# rollout: {msg}", file=sys.stderr, flush=True)

    model = {"vocab_size": 101, "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 2, "max_position_embeddings": 128}
    seed = int(os.environ.get("BENCH_ROLLOUT_SEED", "0"))
    n_req = int(os.environ.get("BENCH_ROLLOUT_REQUESTS", "48"))
    n_new = int(os.environ.get("BENCH_ROLLOUT_NEW_TOKENS", "8"))
    canary_fraction = 0.5

    gcfg = GPT2Config(**model, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    _params = {}    # init seed -> params (the per-generation oracles)
    _oracle_cache = {}

    def reference(init_seed, prompt):
        key = (init_seed, tuple(prompt))
        if key not in _oracle_cache:
            if init_seed not in _params:
                _, _params[init_seed] = init_gpt2(
                    gcfg, batch_size=1, seq_len=8, seed=init_seed)
            _oracle_cache[key] = np.asarray(generate(
                _params[init_seed], gcfg, np.asarray([prompt], np.int32),
                n_new))[0].tolist()
        return _oracle_cache[key]

    tmp = tempfile.mkdtemp(prefix="rollout_bench_")
    ckpt_root = os.path.join(tmp, "ckpts")
    storage = CheckpointStorage()

    def commit_tag(tag, init_seed):
        w = storage.tag_writer(ckpt_root, tag)
        w.write_file("weights.json",
                     json.dumps({"seed": init_seed}).encode())
        w.commit()

    def config_for_generation(tag):
        """Weight tag -> replica config booted on that tag's init seed
        (the tiny-model stand-in for loading the tag's weights)."""
        with open(os.path.join(ckpt_root, tag, "weights.json")) as f:
            init_seed = int(json.load(f)["seed"])
        path = os.path.join(tmp, f"replica-{tag}.json")
        if not os.path.exists(path):
            with open(path, "w") as f:
                json.dump({"model": model, "seed": init_seed,
                           "ds_config": {"train_batch_size": 1,
                                         "serving": {"max_slots": 4,
                                                     "max_queue": 16,
                                                     "max_seq_len": 128}}},
                          f)
        return path

    commit_tag("v1", 0)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    spawner = ProcessReplicaSpawner(
        config_for_generation("v1"), env=env,
        config_for_generation=config_for_generation)

    streams = {}
    oops = []            # idempotency-oracle violations, described

    def stream_cb(key, tok):
        streams.setdefault(key, []).append(tok)

    router = None
    controller = None
    t_wall = time.perf_counter()
    try:
        progress("spawning 2 incumbent replicas on tag v1 (compile)")
        incumbents = [spawner.spawn(f"inc-{i}", generation="v1")
                      for i in range(2)]
        router = Router(
            [h.endpoint() for h in incumbents],
            FleetConfig(enabled=True, retry_budget=3, retry_backoff_s=0.05,
                        attempt_timeout_s=300.0, health_ttl_s=0.1,
                        saturation_queue_depth=8, shed_retry_after_s=0.1,
                        affinity_prefix_tokens=4))
        for i in range(2):      # land compiles before any recovery clock
            router.submit([2 + i, 3, 5, 7],
                          max_new_tokens=n_new).result(timeout=600)
        controller = RolloutController(
            router, spawner, ckpt_root,
            config=RolloutConfig(
                enabled=True, canary_fraction=canary_fraction,
                canary_replicas=1, shadow_sample_rate=0.5,
                shadow_max_pending=16, canary_hold_s=0.5,
                min_canary_requests=4, min_shadow_compared=3,
                shadow_diff_threshold=0.0, max_canary_crashes=1,
                poll_interval_s=0.05, recovery_bound_s=30.0),
            replicas=incumbents, incumbent_tag="v1",
            rng=random.Random(seed))

        rng = random.Random(seed)

        def pump(label, done):
            """Submit n_req requests while single-stepping the
            controller, then keep stepping until ``done()``."""
            futs, i = [], 0
            deadline = time.monotonic() + 300.0
            while (i < n_req or not done()) \
                    and time.monotonic() < deadline:
                if i < n_req:
                    prompt = [rng.randrange(2, 90) for _ in range(6)]
                    key = f"{label}-{i}"
                    try:
                        futs.append((key, prompt, router.submit(
                            prompt, max_new_tokens=n_new,
                            stream_cb=stream_cb, key=key,
                            shed_retries=20)))
                    except Exception as e:
                        oops.append(f"{key}: submit failed: {e!r}")
                    i += 1
                controller.step()
                time.sleep(0.01)
            return futs, done()

        def settle(futs):
            """Resolve every future against the idempotency oracle.
            Returns (completed, dropped, duplicated)."""
            completed = dropped = duplicated = 0
            for key, prompt, fut in futs:
                try:
                    tokens = fut.result(timeout=300.0)
                except Exception as e:
                    dropped += 1
                    oops.append(f"{key}: lost: {e!r}")
                    continue
                completed += 1
                s = streams.get(key, [])
                if len(s) > len(tokens) \
                        or (len(s) == len(tokens) and s != tokens):
                    duplicated += 1
                    oops.append(f"{key}: stream/result divergence")
                elif len(s) < len(tokens):
                    dropped += 1
                    oops.append(f"{key}: stream dropped tokens")
                elif tokens not in (reference(0, prompt),
                                    reference(1, prompt)):
                    oops.append(f"{key}: matches no single generation")
            return completed, dropped, duplicated

        # -- phase 1: roll-forward on identical weights ------------------
        progress("committing tag v2 (same weights) — expecting promote")
        commit_tag("v2", 0)
        futs, ok = pump("fwd", lambda: controller.current_tag == "v2")
        m_fwd = controller.metrics.snapshot()
        eps = {ep.generation for ep in router.endpoints()}
        rollforward_ok = bool(ok) and eps == {"v2"}
        c1, d1, dup1 = settle(futs)
        rollforward_ok = rollforward_ok and not oops
        progress(f"roll-forward: phase={controller.phase} "
                 f"generations={sorted(eps)} completed={c1}")

        # -- phase 2: forced regression on different weights -------------
        controller.drive(until=("idle",), timeout_s=10.0)
        progress("committing tag v3 (regressed weights) — expecting "
                 "rollback")
        commit_tag("v3", 1)
        futs, ok = pump(
            "bad", lambda: (controller.metrics.rollbacks_total >= 1
                            and controller.phase == "idle"))
        m_bad = controller.metrics.snapshot()
        eps = {ep.generation for ep in router.endpoints()}
        rollback_ok = (bool(ok) and eps == {"v2"}
                       and controller.current_tag == "v2"
                       and controller.metrics.last_rollback_reason
                       == "shadow_diff")
        c2, d2, dup2 = settle(futs)
        rollback_ok = rollback_ok and not oops
        recovery_s = controller.metrics.last_recovery_s
        progress(f"rollback: phase={controller.phase} "
                 f"reason={controller.metrics.last_rollback_reason!r} "
                 f"recovery={recovery_s}s completed={c2}")
        for msg in oops:
            progress(f"ORACLE VIOLATION: {msg}")

        canary_routed = int(router.counters().get("canary_routed", 0))
    finally:
        if controller is not None:
            controller.stop()
        if router is not None:
            router.close()
        spawner.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)

    result = {
        "platform": "cpu",
        "model": "gpt2-tiny(L2,H32)",
        "rollout_seed": seed,
        "canary_fraction": canary_fraction,
        "requests_total": 2 * n_req,
        "completed_total": c1 + c2,
        "dropped_total": d1 + d2,
        "duplicated_total": dup1 + dup2,
        "canary_routed_total": canary_routed,
        "shadow_compared_total": int(m_fwd["shadow_compared_total"]
                                     + m_bad["shadow_compared_total"]),
        "shadow_diff_total": int(m_fwd["shadow_diff_total"]
                                 + m_bad["shadow_diff_total"]),
        "rollbacks_total": int(m_bad["rollbacks_total"]),
        "rollforward_ok": rollforward_ok,
        "rollback_ok": rollback_ok,
        "rollback_recovery_s": round(float(recovery_s or 0.0), 3),
        "recovery_bound_s": 30.0,
        "wall_s": round(time.perf_counter() - t_wall, 1),
        "complete": True,
    }
    out = os.environ.get("BENCH_ROLLOUT_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ROLLOUT_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": f"weight rollout hot-swap ({2 * n_req} requests, "
                  f"seed {seed}) rollback recovery",
        "value": result["rollback_recovery_s"],
        "unit": "s",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "completed_total", "dropped_total", "duplicated_total",
            "canary_routed_total", "shadow_compared_total",
            "shadow_diff_total", "rollforward_ok", "rollback_ok")},
    }))
    if not (result["rollforward_ok"] and result["rollback_ok"]
            and result["dropped_total"] == 0
            and result["duplicated_total"] == 0):
        return 1
    return 0


def disagg_child_main():
    """Disaggregated prefill/decode leg: the SAME mixed longdoc+chat
    workload driven against two equal-cost topologies — two interleaved
    mixed replicas (baseline) vs one prefill + one decode worker with
    fault-tolerant KV-page handoff — measuring chat TTFT p95 AND
    longdoc decode tokens/sec for both.

    The workload is the disaggregation motivator: each round puts
    sustained longdoc decode load on the fleet, then lands latency-
    sensitive chat prompts in the middle of it. Interleaved replicas run
    the chat prefill inside the same engine loop as the longdoc decode
    steps; the disaggregated prefill worker is decode-free, so chat TTFT
    does not pay for other requests' decode. Every request is checked
    bitwise against the in-process ``generate()`` oracle and its stream
    counted (exactly-once accounting); after each leg every replica must
    drain to zero in-use KV pages and zero pending handoff claims.

    A chaos mini-leg then runs one episode of each disagg fault arm
    (kill prefill mid-handoff, kill decode post-ack, corrupt a page
    frame) on a 2-prefill + 1-decode fleet, recording bounded recovery.

    Writes DISAGG_BENCH_CPU.json (BENCH_DISAGG_OUT redirects, as the
    gate does). The gate's schema check REFUSES dropped or duplicated
    requests, bitwise mismatches, leaked pages, failed chaos invariants,
    and a disagg TTFT p95 that is not better than interleaved."""
    import shutil
    import tempfile
    import random as pyrandom

    import numpy as np

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving.autoscaler import (
        ProcessReplicaSpawner,
        replica_op,
    )
    from deepspeed_tpu.inference.serving.chaos import (
        DISAGG_FAULT_KINDS,
        DisaggChaosHarness,
    )
    from deepspeed_tpu.inference.serving.config import FleetConfig
    from deepspeed_tpu.inference.serving.router import Router
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    def progress(msg):
        print(f"# disagg: {msg}", file=sys.stderr, flush=True)

    model = {"vocab_size": 101, "hidden_size": 128, "num_hidden_layers": 4,
             "num_attention_heads": 4, "max_position_embeddings": 128}
    seed = int(os.environ.get("BENCH_DISAGG_SEED", "0"))
    rounds = int(os.environ.get("BENCH_DISAGG_ROUNDS", "5"))
    long_new = int(os.environ.get("BENCH_DISAGG_LONG_NEW_TOKENS", "40"))
    chat_new = int(os.environ.get("BENCH_DISAGG_CHAT_NEW_TOKENS", "8"))

    gcfg = GPT2Config(**model, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(gcfg, batch_size=1, seq_len=8, seed=0)
    _oracle_cache = {}

    def reference(prompt, max_new):
        key = (tuple(prompt), max_new)
        if key not in _oracle_cache:
            _oracle_cache[key] = np.asarray(generate(
                params, gcfg, np.asarray([prompt], np.int32),
                max_new))[0].tolist()
        return _oracle_cache[key]

    def pctl(xs, p):
        if not xs:
            return 0.0
        xs = sorted(xs)
        return float(xs[min(len(xs) - 1, int(p * len(xs)))])

    def make_workload(rng):
        """One deterministic request schedule, replayed on both legs."""
        schedule = []
        for _ in range(rounds):
            batch = []
            for _ in range(3):
                plen = rng.randint(48, 64)
                batch.append(("longdoc",
                              [rng.randint(1, model["vocab_size"] - 1)
                               for _ in range(plen)], long_new))
            for _ in range(4):
                plen = rng.randint(4, 8)
                batch.append(("chat",
                              [rng.randint(1, model["vocab_size"] - 1)
                               for _ in range(plen)], chat_new))
            schedule.append(batch)
        return schedule

    def pages_drained(router, timeout_s=30.0):
        """Zero-orphan check: every replica back to zero in-use KV lanes
        and zero pending handoff claims (polling doubles as the reaper
        heartbeat). Returns pages still held after the timeout."""
        deadline = time.monotonic() + timeout_s
        leaked = 0
        while time.monotonic() < deadline:
            leaked = 0
            for ep in router.endpoints():
                try:
                    doc = replica_op(ep.host, ep.port, {"op": "health"})
                except OSError:
                    leaked += 1
                    continue
                pool = doc.get("kv_pool") or {}
                leaked += int(pool.get("in_use", 0))
                leaked += int(doc.get("handoff_pending", 0))
            if leaked == 0:
                return 0
            time.sleep(0.1)
        return leaked

    def run_leg(router, schedule, label):
        """Drive the schedule; returns per-kind TTFT/decode-rate stats
        plus the exactly-once accounting."""
        stats = {"submitted": 0, "completed": 0, "dropped": 0,
                 "duplicated": 0, "mismatch": 0,
                 "chat_ttft": [], "long_ttft": [], "decode_tok_s": []}
        for rno, batch in enumerate(schedule):
            inflight = []
            for kind, prompt, n_new in batch:
                if kind == "chat":
                    time.sleep(0.03)    # land mid-decode, one at a time
                times = []
                t0 = time.monotonic()
                fut = router.submit(
                    prompt, max_new_tokens=n_new,
                    stream_cb=lambda k, t, ts=times: ts.append(
                        time.monotonic()),
                    shed_retries=5)
                stats["submitted"] += 1
                inflight.append((kind, prompt, n_new, t0, times, fut))
                if kind == "longdoc":
                    time.sleep(0.01)
            # let longdoc decode build up before the chats arrive
            for kind, prompt, n_new, t0, times, fut in inflight:
                try:
                    tokens = list(fut.result(timeout=300))
                except Exception as e:
                    progress(f"{label} round {rno}: {kind} failed "
                             f"{type(e).__name__}: {e}")
                    stats["dropped"] += 1
                    continue
                stats["completed"] += 1
                if tokens != reference(prompt, n_new):
                    stats["mismatch"] += 1
                if len(times) > len(tokens):
                    stats["duplicated"] += 1
                elif len(times) < len(tokens):
                    stats["dropped"] += 1
                if times:
                    ttft = times[0] - t0
                    stats["chat_ttft" if kind == "chat"
                          else "long_ttft"].append(ttft)
                if len(times) >= 2 and times[-1] > times[0]:
                    stats["decode_tok_s"].append(
                        (len(times) - 1) / (times[-1] - times[0]))
        return stats

    tmp = tempfile.mkdtemp(prefix="disagg_bench_")
    cfg_path = os.path.join(tmp, "replica.json")
    with open(cfg_path, "w") as f:
        json.dump({"model": model, "seed": 0, "chaos": True,
                   "ds_config": {"train_batch_size": 1,
                                 "serving": {"max_slots": 8, "max_queue": 32,
                                             "max_seq_len": 128},
                                 "fleet": {"handoff": {
                                     "attempt_timeout_s": 60.0,
                                     "retries": 3, "backoff_s": 0.02,
                                     "backoff_max_s": 0.2,
                                     "claim_ttl_s": 2.0,
                                     "resume_ttl_s": 4.0}}}}, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    fleet_cfg = dict(enabled=True, retry_budget=4, retry_backoff_s=0.05,
                     attempt_timeout_s=300.0, health_ttl_s=0.1,
                     saturation_queue_depth=16, shed_retry_after_s=0.1,
                     affinity_prefix_tokens=0)
    schedule = make_workload(pyrandom.Random(seed))
    warm_long = schedule[0][0][1]
    warm_chat = schedule[0][3][1]
    t_wall = time.perf_counter()

    def warm(router, n_each):
        # land both prompt buckets AND the decode path on every replica
        # before any clock starts
        for _ in range(n_each):
            router.submit(warm_long, max_new_tokens=4).result(timeout=600)
            router.submit(warm_chat, max_new_tokens=4).result(timeout=600)

    spawner = ProcessReplicaSpawner(cfg_path, env=env)
    inter = disagg = chaos_report = None
    leaked_total = 0
    handoff_counters = {}
    try:
        # -- leg A: two interleaved mixed replicas ----------------------
        progress("leg A: spawning 2 interleaved mixed replicas (compile)")
        mixed = [spawner.spawn("m0"), spawner.spawn("m1")]
        router = Router([h.endpoint() for h in mixed],
                        FleetConfig(**fleet_cfg))
        try:
            warm(router, 2)
            progress(f"leg A: {rounds} rounds")
            inter = run_leg(router, schedule, "interleaved")
            leaked_total += pages_drained(router)
        finally:
            router.close()
        for h in mixed:
            spawner.drain(h, wait_s=5.0)

        # -- leg B: one prefill + one decode worker ---------------------
        progress("leg B: spawning 1 prefill + 1 decode replica (compile)")
        pre = spawner.spawn("p0", role="prefill")
        dec = spawner.spawn("d0", role="decode")
        router = Router([pre.endpoint(), dec.endpoint()],
                        FleetConfig(**fleet_cfg))
        try:
            warm(router, 2)
            progress(f"leg B: {rounds} rounds")
            disagg = run_leg(router, schedule, "disagg")
            leaked_total += pages_drained(router)
            handoff_counters = {
                k: v for k, v in router.counters().items()
                if k.startswith("handoff_")}

            # -- chaos mini-leg on a 2-prefill + 1-decode fleet ---------
            progress("chaos mini-leg: +1 prefill replica, one episode "
                     "per disagg fault arm")
            pre2 = spawner.spawn("p1", role="prefill")
            router.add_endpoint(pre2.endpoint())
            warm(router, 1)
            harness = DisaggChaosHarness(
                router, spawner, reference, [pre, pre2, dec],
                seed=seed, max_new_tokens=chat_new,
                request_timeout_s=300.0, recovery_timeout_s=300.0,
                vocab=model["vocab_size"])
            for kind in DISAGG_FAULT_KINDS:
                ep = harness.run_episode(kind=kind)
                progress(f"chaos {kind}: completed={ep['completed']} "
                         f"fired={ep.get('fired')} "
                         f"recovery={ep.get('recovery_s', -1):.2f}s "
                         f"pages_clean={ep['pages_clean']}")
            chaos_report = harness.report()
        finally:
            router.close()
    finally:
        spawner.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)

    inter_ttft = pctl(inter["chat_ttft"], 0.95)
    disagg_ttft = pctl(disagg["chat_ttft"], 0.95)
    result = {
        "platform": "cpu",
        "model": "gpt2-tiny(L4,H128)",
        "rounds": rounds,
        "requests_per_leg": inter["submitted"],
        "long_new_tokens": long_new,
        "chat_new_tokens": chat_new,
        "interleaved_ttft_p95_s": round(inter_ttft, 4),
        "disagg_ttft_p95_s": round(disagg_ttft, 4),
        "interleaved_ttft_p50_s": round(pctl(inter["chat_ttft"], 0.5), 4),
        "disagg_ttft_p50_s": round(pctl(disagg["chat_ttft"], 0.5), 4),
        # the headline: how much cheaper the p95 chat TTFT gets when
        # prefill stops paying for other requests' decode
        "ttft_improvement": round(inter_ttft / max(disagg_ttft, 1e-9), 3),
        "interleaved_decode_tok_s": round(
            pctl(inter["decode_tok_s"], 0.5), 2),
        "disagg_decode_tok_s": round(
            pctl(disagg["decode_tok_s"], 0.5), 2),
        "handoffs_total": int(handoff_counters.get("handoff_routed", 0)),
        "handoffs_completed": int(
            handoff_counters.get("handoff_completed", 0)),
        "handoffs_failed": int(handoff_counters.get("handoff_failed", 0)),
        "completed_total": inter["completed"] + disagg["completed"],
        "dropped_total": inter["dropped"] + disagg["dropped"],
        "duplicated_total": inter["duplicated"] + disagg["duplicated"],
        "bitwise_mismatch_total": inter["mismatch"] + disagg["mismatch"],
        "leaked_pages_total": leaked_total,
        "chaos_episodes": chaos_report["chaos_episodes"],
        "chaos_faults_fired": chaos_report["handoff_faults_fired"],
        "chaos_recovery_max_s": chaos_report["recovery_max_s"],
        "chaos_bitwise_ok": chaos_report["invariant_bitwise_ok"],
        "chaos_no_stuck": chaos_report["invariant_no_stuck"],
        "chaos_recovery_bounded": chaos_report[
            "invariant_recovery_bounded"],
        "chaos_pages_clean": chaos_report["invariant_pages_clean"],
        "wall_s": round(time.perf_counter() - t_wall, 1),
        "complete": True,
    }
    out = os.environ.get("BENCH_DISAGG_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "DISAGG_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": f"disaggregated prefill/decode chat TTFT p95 "
                  f"({rounds} rounds, seed {seed}) vs interleaved",
        "value": result["ttft_improvement"],
        "unit": "x interleaved TTFT p95",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "interleaved_ttft_p95_s", "disagg_ttft_p95_s",
            "interleaved_decode_tok_s", "disagg_decode_tok_s",
            "handoffs_total", "dropped_total", "duplicated_total",
            "bitwise_mismatch_total", "leaked_pages_total",
            "chaos_bitwise_ok", "chaos_pages_clean")},
    }))
    if not (result["ttft_improvement"] > 1.0
            and result["dropped_total"] == 0
            and result["duplicated_total"] == 0
            and result["bitwise_mismatch_total"] == 0
            and result["leaked_pages_total"] == 0
            and result["chaos_bitwise_ok"] and result["chaos_no_stuck"]
            and result["chaos_recovery_bounded"]
            and result["chaos_pages_clean"]):
        return 1
    return 0


def train_child_main():
    """Train-step fusion leg: overlapped per-bucket backward/reduce-scatter +
    donated buffers vs the sequential post-backward reduce, plus interleaved
    1F1B bubble accounting — the DeepCompile-style proof harness on a
    simulated 4-device CPU mesh.

    Three measurements, all refusable by the bench gate's schema check so a
    regressed baseline can never be committed:

    1. PARITY: the overlapped+donated fused step must reproduce the
       sequential step's losses AND final params BITWISE (fp32) over
       ``BENCH_TRAIN_PARITY_STEPS`` distinct batches (``parity_ok``).
    2. OVERLAP: per-bucket collective structure verified from the compiled
       HLO (reduce-scatter + all-reduce counts track the bucket plan; the
       CPU backend lowers reduce-scatter as all-reduce, so both spellings
       are counted), and steady-state step_ms from min-of-
       ``BENCH_TRAIN_WINDOWS`` timed chains (CPU wall noise makes a single
       window untrustworthy). "Sequential" is the SINGLE-BUCKET tap: the
       identical pin machinery, but the one monolithic reduce can only
       complete once the whole backward has produced every grad — the
       textbook post-backward reduce. The overlapped variant differs ONLY
       in granularity (N buckets, each pinned where its grads appear), so
       the pair isolates reduce *placement*, which is the claim under
       test — not the tap's constant materialization cost. That cost is
       reported honestly as ``baseline_step_ms``: the untapped program
       whose single reduce XLA schedules wherever it likes (ungated —
       on CPU there is no async collective engine, so pinning anything
       can only cost; on TPU the pin is what buys the overlap).
    3. INTERLEAVING: a REAL S=4 pipeline trained at V=1 and V=2 over the
       same data (losses must match — same composition, different
       schedule), with the schedule-simulator bubble fractions the engines
       themselves export as Train/Pipe/bubble_frac. At S=4, M=8 the
       interleaved bubble (0.158) must be strictly below 1F1B's (0.273).

    Writes TRAIN_BENCH_CPU.json (BENCH_TRAIN_OUT redirects, as the gate
    does). Knobs: BENCH_TRAIN_HIDDEN/DEPTH/MB/BUCKET/STEPS/WINDOWS/
    PARITY_STEPS/PIPE_STEPS."""
    # pin the simulated mesh BEFORE jax initializes (this leg is CPU-only:
    # it proves program structure and schedule math, not chip throughput)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
    import numpy as np
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule

    def progress(msg):
        print(f"# train: {msg}", file=sys.stderr, flush=True)

    hidden = int(os.environ.get("BENCH_TRAIN_HIDDEN", "64"))
    depth = int(os.environ.get("BENCH_TRAIN_DEPTH", "4"))
    mb_rows = int(os.environ.get("BENCH_TRAIN_MB", "8"))
    bucket = int(os.environ.get("BENCH_TRAIN_BUCKET", "4096"))
    steps = int(os.environ.get("BENCH_TRAIN_STEPS", "30"))
    windows = int(os.environ.get("BENCH_TRAIN_WINDOWS", "3"))
    parity_steps = int(os.environ.get("BENCH_TRAIN_PARITY_STEPS", "4"))
    pipe_steps = int(os.environ.get("BENCH_TRAIN_PIPE_STEPS", "2"))
    n_dev = len(jax.devices())
    t_wall = time.perf_counter()

    class _MLP(nn.Module):
        hidden: int
        depth: int

        @nn.compact
        def __call__(self, x, y):
            h = x
            for _ in range(self.depth):
                h = nn.tanh(nn.Dense(self.hidden)(h))
            out = nn.Dense(x.shape[-1])(h)
            return jnp.mean((out.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)

    rng = np.random.RandomState(7)
    feat = hidden
    data = [(rng.randn(mb_rows * n_dev, feat).astype(np.float32),
             rng.randn(mb_rows * n_dev, feat).astype(np.float32))
            for _ in range(parity_steps)]

    def make_engine(overlap, bucket_size):
        model = _MLP(hidden=hidden, depth=depth)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, feat)), jnp.zeros((1, feat)))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config_params={
                "train_batch_size": mb_rows * n_dev,
                "train_micro_batch_size_per_gpu": mb_rows,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2, "overlap_comm": overlap,
                                      "reduce_bucket_size": bucket_size},
            })
        return engine

    def collective_count(engine):
        engine._ensure_opt_state()
        fused = engine._get_train_step(engine._module_needs_rng(), 2)
        inner = getattr(fused, "_fn", fused)  # unwrap the CompileSentinel
        x = jnp.zeros((1, mb_rows * n_dev, feat), jnp.float32)
        lowered = inner.lower(
            engine.params, engine.opt_state, engine.scaler_state,
            jax.random.PRNGKey(0), jnp.float32(1.0), jnp.float32(1e-3), x, x)
        txt = lowered.compile().as_text()
        return txt.count("reduce-scatter(") + txt.count("all-reduce(")

    # -- 1. parity (bitwise, fp32) --------------------------------------
    # three variants: untapped baseline, single-bucket tap (sequential
    # post-backward reduce), N-bucket tap (overlapped). The tap is the
    # identity, so ALL THREE must train bitwise-identically.
    progress("parity: baseline vs sequential(1-bucket) vs overlapped tap")
    results = {}
    for name, overlap, bsz in (("base", False, bucket),
                               ("seq", True, 1 << 62),
                               ("ovl", True, bucket)):
        eng = make_engine(overlap, bsz)
        losses = [float(jax.device_get(eng.train_step([b]))) for b in data]
        results[name] = (losses, jax.device_get(eng.params), eng)
    base_losses, base_params, base_eng = results["base"]
    seq_losses, seq_params, seq_eng = results["seq"]
    ovl_losses, ovl_params, ovl_eng = results["ovl"]

    def same_params(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    parity = (base_losses == seq_losses == ovl_losses
              and same_params(base_params, seq_params)
              and same_params(seq_params, ovl_params))
    n_buckets = len(getattr(ovl_eng.optimizer, "bucket_numels", None) or ())
    seq_buckets = len(getattr(seq_eng.optimizer, "bucket_numels", None) or ())
    progress(f"parity={parity} buckets={n_buckets} (seq={seq_buckets})")

    # -- 2. collective structure + steady-state step time ----------------
    coll_seq = collective_count(seq_eng)
    coll_ovl = collective_count(ovl_eng)
    progress(f"collectives: seq={coll_seq} overlapped={coll_ovl}")

    def window_ms(engine):
        batch = data[0]
        loss = engine.train_step([batch])
        float(jax.device_get(loss))  # absorb compile + warm the chain
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_step([batch])
        float(jax.device_get(loss))
        return (time.perf_counter() - t0) / steps * 1000.0

    # ALTERNATE the engines' windows so slow drift on a shared box
    # (cache pressure, sibling jobs) hits every variant equally, then
    # take each engine's floor — the minima are the comparison
    window_ms(base_eng), window_ms(seq_eng), window_ms(ovl_eng)  # throwaway
    base_ms = seq_ms = ovl_ms = None
    for _ in range(windows):
        b = window_ms(base_eng)
        s = window_ms(seq_eng)
        o = window_ms(ovl_eng)
        base_ms = b if base_ms is None else min(base_ms, b)
        seq_ms = s if seq_ms is None else min(seq_ms, s)
        ovl_ms = o if ovl_ms is None else min(ovl_ms, o)
    progress(f"step_ms: baseline={base_ms:.3f} seq={seq_ms:.3f} "
             f"overlapped={ovl_ms:.3f}")

    # -- 3. interleaved pipeline: real run + schedule bubble --------------
    pipe_S, pipe_M = 4, 8

    class _PipeDense(nn.Module):
        features: int

        @nn.compact
        def __call__(self, x):
            return nn.tanh(nn.Dense(self.features)(x))

    def pipe_losses(chunks):
        layers = [LayerSpec(_PipeDense, features=feat) for _ in range(8)]
        module = PipelineModule(
            layers, num_stages=pipe_S,
            loss_fn=lambda out, label: jnp.mean(
                (out.astype(jnp.float32) - label.astype(jnp.float32)) ** 2),
            base_seed=11, partition_method="uniform")
        cfg = {"train_batch_size": mb_rows * pipe_M,
               "train_micro_batch_size_per_gpu": mb_rows,
               "gradient_accumulation_steps": pipe_M,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "pipeline": {"executor": "interpreted"}}
        if chunks > 1:
            cfg["pipeline"]["num_model_chunks"] = chunks
        engine, _, _, _ = deepspeed_tpu.initialize(model=module,
                                                   config_params=cfg)
        prng = np.random.RandomState(13)
        batches = iter([
            (prng.randn(mb_rows, feat).astype(np.float32),
             prng.randn(mb_rows, feat).astype(np.float32))
            for _ in range(pipe_steps * pipe_M)])
        losses = [engine.train_batch(batches) for _ in range(pipe_steps)]
        return losses, engine._schedule_bubble_fraction(), \
            engine._est_parallel_step_s() * 1000.0

    progress(f"pipeline S={pipe_S} M={pipe_M}: V=1 vs V=2")
    pl1, bub1, est1 = pipe_losses(1)
    pl2, bub2, est2 = pipe_losses(2)
    pipe_match = bool(np.allclose(pl1, pl2, rtol=1e-6, atol=1e-7))
    progress(f"pipe losses match={pipe_match} bubble {bub1:.4f} -> {bub2:.4f}")

    result = {
        "platform": "cpu",
        "model": f"mlp(d{depth},h{hidden})+pipe8x{feat}",
        "train_fusion": True,
        "n_devices": n_dev,
        "zero_stage": 2,
        "reduce_bucket_size": bucket,
        "reduce_buckets": n_buckets,
        "parity_ok": bool(parity),
        "parity_steps": parity_steps,
        "baseline_step_ms": round(base_ms, 3),
        "seq_step_ms": round(seq_ms, 3),
        "overlap_step_ms": round(ovl_ms, 3),
        "overlap_vs_seq": round(ovl_ms / seq_ms, 4) if seq_ms else None,
        "collectives_seq": coll_seq,
        "collectives_overlap": coll_ovl,
        "comm_overlap_frac": round((n_buckets - 1) / n_buckets, 4) if n_buckets else 0.0,
        "pipe_stages": pipe_S,
        "pipe_micro_batches": pipe_M,
        "pipe_loss_match": pipe_match,
        "bubble_1f1b": round(bub1, 4),
        "bubble_interleaved": round(bub2, 4),
        "pipe_est_step_ms_1f1b": round(est1, 2),
        "pipe_est_step_ms_interleaved": round(est2, 2),
        "wall_s": round(time.perf_counter() - t_wall, 1),
        "complete": True,
    }
    out = os.environ.get("BENCH_TRAIN_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "TRAIN_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": "fused train step, overlapped vs sequential reduce "
                  "(4-dev CPU mesh)",
        "value": result["overlap_step_ms"],
        "unit": "ms/step",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "seq_step_ms", "overlap_vs_seq", "parity_ok", "reduce_buckets",
            "collectives_seq", "collectives_overlap", "pipe_loss_match",
            "bubble_1f1b", "bubble_interleaved")},
    }))
    if not (parity and pipe_match and bub2 < bub1):
        return 1
    return 0


def offload_child_main():
    """Bucket-streamed ZeRO-Offload leg: the three-stage host pipeline
    (per-bucket async D2H -> background host Adam -> H2D commit) vs the
    sequential offload step, on CPU where the mechanism is thread overlap
    (device_get memcpy, GIL-releasing numpy Adam, and device_put memcpy
    run on three threads; wall approaches max of the stage sums instead
    of their total).

    Two measurements, both refusable by the bench gate's schema check:

    1. PARITY: streamed (K buckets) and sequential (K=1) engines train the
       SAME jitted program (both overlap_comm=false) over
       ``BENCH_OFFLOAD_PARITY_STEPS`` distinct batches — losses, final
       params, AND the host fp32 master must match BITWISE
       (``parity_ok``/``master_parity_ok``), and the streamed run must
       compile exactly once (``one_compile``).
    2. SPEED: steady-state step_ms from min-of-``BENCH_OFFLOAD_WINDOWS``
       alternating timed chains; ``streamed_vs_seq`` < 1.0 is the claim.
       The model is sized (``BENCH_OFFLOAD_HIDDEN/DEPTH``) so the host
       optimizer tier dominates the step — the regime offload targets.

    Writes OFFLOAD_BENCH_CPU.json (BENCH_OFFLOAD_OUT redirects, as the
    gate does). Knobs: BENCH_OFFLOAD_HIDDEN/DEPTH/ROWS/BUCKETS/STEPS/
    WINDOWS/PARITY_STEPS."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    import deepspeed_tpu
    from deepspeed_tpu.profiling.sentinels import compile_cache_size

    def progress(msg):
        print(f"# offload: {msg}", file=sys.stderr, flush=True)

    hidden = int(os.environ.get("BENCH_OFFLOAD_HIDDEN", "768"))
    depth = int(os.environ.get("BENCH_OFFLOAD_DEPTH", "6"))
    rows = int(os.environ.get("BENCH_OFFLOAD_ROWS", "8"))
    k_buckets = int(os.environ.get("BENCH_OFFLOAD_BUCKETS", "3"))
    steps = int(os.environ.get("BENCH_OFFLOAD_STEPS", "10"))
    windows = int(os.environ.get("BENCH_OFFLOAD_WINDOWS", "3"))
    parity_steps = int(os.environ.get("BENCH_OFFLOAD_PARITY_STEPS", "4"))
    t_wall = time.perf_counter()

    class _MLP(nn.Module):
        hidden: int
        depth: int

        @nn.compact
        def __call__(self, x, y):
            h = x
            for _ in range(self.depth):
                h = jnp.tanh(nn.Dense(self.hidden)(h))
            out = nn.Dense(x.shape[-1])(h)
            return jnp.mean((out.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)

    rng = np.random.RandomState(11)
    data = [(rng.randn(rows, hidden).astype(np.float32),
             rng.randn(rows, hidden).astype(np.float32))
            for _ in range(parity_steps)]

    def make_engine(stream_buckets):
        model = _MLP(hidden=hidden, depth=depth)
        params = model.init(jax.random.PRNGKey(5),
                            jnp.zeros((1, hidden)), jnp.zeros((1, hidden)))
        # both engines run overlap_comm=false so the jitted fwd/bwd program
        # is IDENTICAL — the streamed/sequential difference is host-side
        # only, which is what makes bitwise loss parity a structural
        # guarantee rather than a numerical accident
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config_params={
                "train_batch_size": rows,
                "train_micro_batch_size_per_gpu": rows,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 10_000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 2, "cpu_offload": True,
                    "offload_stream_buckets": stream_buckets},
            })
        return engine

    def run_steps(engine, batches):
        losses = []
        for x, y in batches:
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(jax.device_get(loss)))
        return losses

    # -- 1. parity (bitwise, fp32) --------------------------------------
    progress(f"parity: sequential(K=1) vs streamed(K={k_buckets}) over "
             f"{parity_steps} batches")
    seq_eng = make_engine(1)
    str_eng = make_engine(k_buckets)
    seq_losses = run_steps(seq_eng, data)
    str_losses = run_steps(str_eng, data)

    def same_params(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a)),
                                   jax.tree_util.tree_leaves(jax.device_get(b))))

    parity = bool(seq_losses == str_losses
                  and same_params(seq_eng.params, str_eng.params))
    master_parity = bool(np.array_equal(seq_eng.optimizer._host_master,
                                        str_eng.optimizer._host_master))
    one_compile = compile_cache_size(str_eng._get_fwd_bwd(False)) == 1
    n_buckets = len(str_eng.optimizer._buckets or ())
    n_params = int(str_eng.optimizer._host_master.size)
    progress(f"parity={parity} master={master_parity} one_compile={one_compile} "
             f"buckets={n_buckets} params={n_params}")

    # -- 2. steady-state step time ---------------------------------------
    def window_ms(engine):
        batch = data[0]
        t0 = time.perf_counter()
        run_steps(engine, [batch] * steps)
        return (time.perf_counter() - t0) / steps * 1000.0

    # alternate windows so shared-box drift hits both variants equally,
    # then take each engine's floor
    window_ms(seq_eng), window_ms(str_eng)  # throwaway warm window
    seq_ms = str_ms = None
    for _ in range(windows):
        s = window_ms(seq_eng)
        o = window_ms(str_eng)
        seq_ms = s if seq_ms is None else min(seq_ms, s)
        str_ms = o if str_ms is None else min(str_ms, o)
    stats = str_eng.optimizer.last_offload_stats or {}
    progress(f"step_ms: sequential={seq_ms:.3f} streamed={str_ms:.3f} "
             f"overlap_frac={stats.get('overlap_frac')}")

    sync_fetches = 0
    try:
        from deepspeed_tpu import telemetry
        c = telemetry.get_registry().counter("Train/offload_sync_fetch_total")
        sync_fetches = int(c.value)
    except Exception:
        pass

    result = {
        "platform": "cpu",
        "model": f"mlp(d{depth},h{hidden})",
        "zero_stage": 2,
        "cpu_offload": True,
        "stream_buckets": n_buckets,
        "params": n_params,
        "parity_ok": parity,
        "master_parity_ok": master_parity,
        "one_compile": bool(one_compile),
        "parity_steps": parity_steps,
        "seq_step_ms": round(seq_ms, 3),
        "streamed_step_ms": round(str_ms, 3),
        "streamed_vs_seq": round(str_ms / seq_ms, 4) if seq_ms else None,
        "offload_overlap_frac": round(float(stats.get("overlap_frac", 0.0)), 4),
        "offload_d2h_ms": round(float(stats.get("d2h_ms", 0.0)), 3),
        "offload_host_step_ms": round(float(stats.get("host_step_ms", 0.0)), 3),
        "offload_h2d_ms": round(float(stats.get("h2d_ms", 0.0)), 3),
        "sync_fetch_fallbacks": sync_fetches,
        "wall_s": round(time.perf_counter() - t_wall, 1),
        "complete": True,
    }
    out = os.environ.get("BENCH_OFFLOAD_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "OFFLOAD_BENCH_CPU.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps({
        "metric": "ZeRO-Offload step, bucket-streamed vs sequential host "
                  "optimizer (CPU)",
        "value": result["streamed_step_ms"],
        "unit": "ms/step",
        "vs_baseline": None,
        **{k: result[k] for k in (
            "seq_step_ms", "streamed_vs_seq", "parity_ok",
            "master_parity_ok", "one_compile", "stream_buckets",
            "offload_overlap_frac")},
    }))
    if not (parity and master_parity and one_compile):
        return 1
    return 0


def _attn_impl_label():
    """Which attention core the step that ran was traced with (see
    ``attention.traced_implementation``)."""
    from deepspeed_tpu.ops.transformer.attention import traced_implementation

    return traced_implementation()


def _attn_rows_per_step():
    """(batch, head) rows a grid step of the flash kernels that step was
    traced with took; 0 where no kernel was traced (the materialised path
    or the jnp reference ran)."""
    from deepspeed_tpu.ops.transformer.attention import traced_rows_per_step

    return traced_rows_per_step()


# ---------------------------------------------------------------------------
# parent: orchestration (never initialises a JAX backend)
# ---------------------------------------------------------------------------

def _run_child(env_extra, timeout):
    """Run the measured benchmark in a subprocess.

    Returns (json_dict|None, err, oom) — ``oom`` is True when the child died
    on an HBM allocation failure, which tells the parent to retry one rung
    down the micro-batch ladder.
    """
    env = dict(os.environ)
    env.update(env_extra)
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
        )
    except subprocess.TimeoutExpired:
        return None, f"bench child timed out after {timeout}s", False
    if r.returncode == 0:
        for line in reversed(r.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line), None, False
                except json.JSONDecodeError:
                    continue
    blob = (r.stderr or "") + (r.stdout or "")
    oom = any(s in blob for s in (
        "RESOURCE_EXHAUSTED", "Out of memory", "out of memory", "AllocateBuffer",
    ))
    return None, f"rc={r.returncode}: {blob.strip()[-400:]}", oom


def main():
    child_timeout = int(os.environ.get("BENCH_TIMEOUT", "1500"))

    # OOM-retry ladder: one allocation failure drops the micro-batch a rung
    # and tries again (the result line says which micro-batch ran). The
    # default start matches the child's per-model default (GPT-2 at seq1024
    # is 16x BERT-seq128 activations per row); capped at 4 rungs so callers
    # can budget ladder_len x BENCH_TIMEOUT.
    model_default = "64" if os.environ.get("BENCH_MODEL", "bert") == "bert" else "8"
    start_mb = int(os.environ.get("BENCH_BATCH", model_default))
    ladder = ([start_mb] + [mb for mb in (64, 32, 16, 8, 4, 2, 1) if mb < start_mb])[:4]
    errors = []
    for mb in ladder:
        result, err, oom = _run_child({"BENCH_BATCH": str(mb)}, child_timeout)
        if result is not None:
            print(json.dumps(result))
            return 0
        errors.append(f"bench mb={mb}: {err}")
        if not oom:
            break  # non-OOM failure: smaller batches won't help
    print("bench.py: no measurement: " + "; ".join(errors), file=sys.stderr)
    return 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(child_main())
    sys.exit(main())
