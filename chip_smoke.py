"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the two main paths through the entry points a user calls,
at the full published width of a model each, on every TPU device the host
shows (one chip, or the four-chip host):

- **kernels**: every Pallas kernel the repo ships, compiled natively and
  compared on device with its XLA or jnp twin at the shapes the two models
  below use (the training attention's materialised path, which BERT-large
  takes at seq 128 without dropout, beside the flash kernels);
- **train**: ``deepspeed_tpu.initialize`` on BERT-large (seq 128, micro-batch
  64 per chip, bf16, dropout, activation checkpointing, ZeRO-2 when there is
  more than one device), a few warm-up steps and then measured
  ``engine.train_step`` calls on fresh synthetic batches;
- **serve**: ``ServingEngine`` on GPT-2 large (36 layers, hidden 1280, 20
  heads: the GPT-2 width whose heads split over four chips) answering a
  handful of requests through ``start()``/``submit()``/futures, two of them
  checked token for token against one-shot ``generate()``.

Weights are random, from a seed; nothing is read from disk or the network.
Any failed check raises, so the exit code is non-zero and no result line is
printed. ``main()`` refuses to run without a TPU: JAX on a CPU can say nothing
about the chip. The phase functions take the model config and sizes as
arguments so ``tests/unit/test_chip_smoke.py`` can run them at toy size on the
CPU (``native=False``: Pallas in interpret mode).

    python chip_smoke.py        # on a TPU host; last stdout line is the result

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

import contextlib
import gc
import json
import logging
import sys
import time

import numpy as np


def _say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_stats():
    """Per-device allocator statistics, or None where the backend keeps none
    (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return stats if all(stats) else None


def _rel_err(got, want):
    """Largest absolute difference as a fraction of the reference's largest
    magnitude: one number that is comparable across fp32, bf16 and int8."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.all(np.isfinite(got)), "non-finite values in kernel output"
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def _check(name, err, tol, report):
    report[name] = round(err, 6)
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3g} exceeds {tol:g}")


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def _banded_causal_layout(heads, n_blocks):
    layout = np.zeros((heads, n_blocks, n_blocks), np.int64)
    for i in range(n_blocks):
        layout[:, i, max(0, i - 1):i + 1] = 1
    return layout


def _flash_legs(report, shapes, dense_shape, block, dropout_rate, native, seed):
    """Training attention through its public entry point. ``shapes`` are
    calls the entry's rule leaves to the flash kernels (their float32 scores
    are over its budget): forward and both backward kernels against the
    dense jnp reference, with and without in-kernel dropout, plus one
    block-sparse layout at the longest shape. ``dense_shape`` is a call the
    rule sends to the materialised path, checked the same way."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer import attention as attn

    rng = np.random.RandomState(seed)

    def sq_loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def against_reference(tag, shape, wanted):
        """Output and gradients of the entry at ``shape`` against the
        reference; raises (``native``) unless the rule chose ``wanted``."""
        q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                   for _ in range(3))
        with jax.default_matmul_precision("highest"):
            want = attn.attention_reference(q, k, v)
            want_g = jax.grad(sq_loss(attn.attention_reference),
                              argnums=(0, 1, 2))(q, k, v)
        traced_before = attn.trace_counts()
        got = attn.flash_attention(q, k, v)
        got_g = jax.grad(sq_loss(attn.flash_attention),
                         argnums=(0, 1, 2))(q, k, v)
        # bf16 in and out: one rounding of the output is 2**-8 of its size
        _check(f"{tag}_fwd", _rel_err(got, want), 3e-2, report)
        _check(f"{tag}_bwd", max(_rel_err(a, b)
                                 for a, b in zip(got_g, want_g)), 5e-2, report)
        traced = attn.traced_implementation(since=traced_before)
        if native and traced != wanted:
            raise AssertionError(
                f"{tag}: flash_attention at {shape} traced {traced!r}, "
                f"wanted {wanted!r}")
        return q, k, v, got

    against_reference(f"attention_dense_s{dense_shape[2]}", dense_shape, "dense")

    for B, H, S, D in shapes:
        tag = f"flash_s{S}"
        q, k, v, got = against_reference(tag, (B, H, S, D), "pallas")
        report[f"{tag}_rows_per_step"] = attn.traced_rows_per_step()

        # dropout: same key, same mask; a different output than without it;
        # and the backward runs (its mask is regenerated, not stored)
        key = jax.random.PRNGKey(seed + S)
        drop = lambda q, k, v: attn.flash_attention(
            q, k, v, dropout_rate=dropout_rate, dropout_rng=key)
        d1, d2 = drop(q, k, v), drop(q, k, v)
        if not bool(jnp.array_equal(d1, d2)):
            raise AssertionError(f"{tag}: dropout not deterministic per key")
        moved = _rel_err(d1, got)
        report[f"{tag}_dropout_shift"] = round(moved, 4)
        if not 0.0 < moved < 2.0:
            raise AssertionError(f"{tag}: dropout moved the output by {moved}")
        drop_g = jax.grad(sq_loss(drop), argnums=(0, 1, 2))(q, k, v)
        if not all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                   for g in drop_g):
            raise AssertionError(f"{tag}: non-finite dropout gradients")

    # keep rate and forward/backward mask agreement, by the identity-V
    # trick: with V = I the output IS the dropped probability matrix, and
    # dL/dV of L = sum(out) is its column sums (S = D = one block)
    S = shapes[0][2]
    rate = 0.3
    key = jax.random.PRNGKey(seed + 7)
    qi = jnp.asarray(rng.randn(1, 2, S, S) * 0.1, jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(S, dtype=jnp.float32), (1, 2, S, S))
    probs = attn.flash_attention(qi, qi, eye, dropout_rate=rate,
                                 dropout_rng=key)
    zero_frac = float(jnp.mean((probs == 0.0).astype(jnp.float32)))
    report["flash_dropout_zero_frac"] = round(zero_frac, 4)
    if abs(zero_frac - rate) > 0.05:
        raise AssertionError(
            f"dropout keep rate off: {zero_frac:.3f} zeros at rate {rate}")
    dv = jax.grad(lambda v_: jnp.sum(attn.flash_attention(
        qi, qi, v_, dropout_rate=rate, dropout_rng=key)))(eye)
    # a wrong backward mask shows as O(1e-2..1); a right one differs by the
    # kernel's bf16 MXU operand rounding only
    _check("flash_dropout_bwd_mask",
           float(jnp.max(jnp.abs(dv[..., 0] - probs.sum(axis=2)))),
           5e-3, report)

    # block-sparse: banded causal layout (the scalar-prefetch LUT path)
    B, H, S, D = shapes[-1]
    layout = _banded_causal_layout(H, S // block)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
               for _ in range(3))
    sparse = lambda q, k, v: attn.flash_attention(
        q, k, v, layout=layout, block=block, causal=True)
    with jax.default_matmul_precision("highest"):
        ref = lambda q, k, v: attn.flash_attention(
            q, k, v, layout=layout, block=block, causal=True,
            force_reference=True)
        want = ref(q, k, v)
        want_g = jax.grad(sq_loss(ref), argnums=(0, 1, 2))(q, k, v)
    _check("flash_sparse_fwd", _rel_err(sparse(q, k, v), want), 3e-2, report)
    got_g = jax.grad(sq_loss(sparse), argnums=(0, 1, 2))(q, k, v)
    _check("flash_sparse_bwd", max(_rel_err(a, b)
                                   for a, b in zip(got_g, want_g)),
           5e-2, report)
    report["flash_sparse_rows_per_step"] = attn.traced_rows_per_step()
    if native and report["flash_sparse_rows_per_step"] != 1:
        raise AssertionError(
            "a block-sparse layout was given several rows a grid step: "
            "its LUT differs by head")


def _serving_kernel_legs(report, heads, head_dim, page_tokens, native, seed):
    """The serving tier: paged decode over fp32, bf16 and int8 pages (the
    one-token step and a prefill-wide chunk) and the banded sink+window
    kernel, each against its XLA twin at full f32 matmul precision."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu import kernels

    rng = np.random.RandomState(seed)
    interp = not native
    nh, hd, pt = heads, head_dim, page_tokens
    lanes, pages_per_lane = 4, 8
    n_pages = lanes * pages_per_lane + 1
    tables = np.stack([1 + rng.permutation(n_pages - 1)[:pages_per_lane]
                       for _ in range(lanes)]).astype(np.int32)
    pk = rng.randn(n_pages, nh, pt, hd).astype(np.float32)
    pv = rng.randn(n_pages, nh, pt, hd).astype(np.float32)
    sk = (np.abs(pk).max(axis=(2, 3)) / 127.0 + 1e-8).astype(np.float32)
    sv = (np.abs(pv).max(axis=(2, 3)) / 127.0 + 1e-8).astype(np.float32)
    quant = lambda x, s: np.clip(np.rint(x / s[:, :, None, None]), -127, 127)
    stores = {
        "fp32": (jnp.asarray(pk), jnp.asarray(pv), {}),
        "bf16": (jnp.asarray(pk, jnp.bfloat16), jnp.asarray(pv, jnp.bfloat16),
                 {}),
        "int8": (jnp.asarray(quant(pk, sk), jnp.int8),
                 jnp.asarray(quant(pv, sv), jnp.int8),
                 {"k_scale": jnp.asarray(sk), "v_scale": jnp.asarray(sv)}),
    }
    chunks = {"step": 1, "chunk": 2 * page_tokens}
    with jax.default_matmul_precision("highest"):
        for cname, C in chunks.items():
            q = jnp.asarray(rng.randn(lanes, C, nh, hd), jnp.float32)
            qpos = jnp.asarray(np.sort(rng.randint(
                0, pages_per_lane * pt, (lanes, C)), axis=1), jnp.int32)
            for sname, (k_, v_, scales) in stores.items():
                if cname == "chunk" and sname != "fp32":
                    continue
                args = (q, k_, v_, jnp.asarray(tables), qpos)
                kw = dict(page_tokens=pt, dtype=jnp.float32, **scales)
                got = kernels.decode_attend(*args, impl="pallas",
                                            interpret=interp, **kw)
                want = kernels.decode_attend(*args, impl="xla", **kw)
                _check(f"decode_{sname}_{cname}", _rel_err(got, want),
                       2e-3, report)

    # bf16 operands multiply exactly on the MXU at any precision setting
    # (and Mosaic refuses an fp32 contraction of bf16 inputs), so only the
    # f32 leg asks for the full-precision reference
    n, W = 8, 2 * pt
    for dtype, precision, tol in ((jnp.float32, "highest", 2e-3),
                                  (jnp.bfloat16, None, 3e-2)):
        mk = lambda *s: jnp.asarray(rng.randn(*s), dtype)
        base = jnp.asarray(rng.randint(0, 6, n) * pt, jnp.int32)
        pos = base + jnp.asarray(rng.randint(0, W, n), jnp.int32)
        args = (mk(n, nh, hd), mk(n, nh, W, hd), mk(n, nh, W, hd),
                mk(n, nh, pt, hd), mk(n, nh, pt, hd), pos, base)
        with jax.default_matmul_precision(precision):
            got = kernels.band_attend(*args, dtype=dtype, impl="pallas",
                                      interpret=interp)
            want = kernels.band_attend(*args, dtype=dtype, impl="xla")
        _check(f"band_{jnp.dtype(dtype).name}", _rel_err(got, want),
               tol, report)

    # the registry's own probes decide what a serving engine gets: on a
    # TPU a failing probe raises here instead of handing out the twin
    for backend in sorted(kernels.KERNEL_BACKENDS):
        impl, interpret = kernels.resolve(backend)
        if (impl, interpret) != ("pallas", interp):
            raise AssertionError(
                f"{backend} resolved to {(impl, interpret)}, "
                f"wanted ('pallas', {interp})")


def kernel_phase(*, flash_shapes, dense_shape, heads, head_dim, page_tokens,
                 native, flash_block=128, dropout_rate=0.1, seed=0):
    """Compile and check every Pallas kernel. ``flash_shapes`` are
    (B, H, S, D) training-attention shapes the entry's rule leaves to the
    kernels, shortest first (S a multiple of ``flash_block``, the
    block-sparse layout's tile), ``dense_shape`` one it sends to the
    materialised path; the serving kernels run at ``heads`` x ``head_dim``
    over ``page_tokens`` pages."""
    report = {}
    _flash_legs(report, flash_shapes, dense_shape, flash_block, dropout_rate,
                native, seed)
    _serving_kernel_legs(report, heads, head_dim, page_tokens, native, seed)
    return report


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def _bert_batches(cfg, global_batch, seq_len, seed):
    """Endless fresh pretraining batches with something to learn: token ids
    follow a Zipf law and the masked-LM label is the token under the mask,
    so the loss falls as soon as the model picks up the unigram statistics
    (uniform random labels would pin it at log(vocab))."""
    rng = np.random.RandomState(seed)
    p = 1.0 / (np.arange(cfg.vocab_size) + 10.0)
    p /= p.sum()
    while True:
        ids = rng.choice(cfg.vocab_size, (global_batch, seq_len), p=p)
        masked = rng.rand(global_batch, seq_len) < 0.15
        yield (ids.astype(np.int32),
               np.zeros((global_batch, seq_len), np.int32),
               np.ones((global_batch, seq_len), np.int32),
               np.where(masked, ids, -1).astype(np.int32),
               rng.randint(0, 2, (global_batch,)).astype(np.int32))


def train_phase(cfg, *, seq_len, micro_batch, warmup, steps, native, seed=0):
    """BERT pretraining through ``deepspeed_tpu.initialize`` and
    ``engine.train_step`` on every device. Raises unless every loss is
    finite, the last is below the first, nothing compiled after warm-up and
    (``native``) the attention that was traced is the one the entry's rule
    names for this call (the kernels with attention dropout, the
    materialised path without at BERT-large's seq 128); on several devices
    also unless ZeRO's state is sharded over all of them and memory is
    spread evenly."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.bert import BertForPreTraining
    from deepspeed_tpu.ops.transformer import attention as attn
    from deepspeed_tpu.profiling.sentinels import CompileSentinel

    n_dev = len(jax.devices())
    global_batch = micro_batch * n_dev
    traced_before = attn.trace_counts()

    model = BertForPreTraining(cfg)
    batches = _bert_batches(cfg, global_batch, seq_len, seed)
    first = tuple(jnp.asarray(x) for x in next(batches))
    t0 = time.perf_counter()
    # jitted: one program (which the persistent cache keeps) instead of an
    # eager op-by-op initialisation that recompiles every run
    params = jax.jit(model.init)({"params": jax.random.PRNGKey(seed),
                                  "dropout": jax.random.PRNGKey(seed + 1)},
                                 *first)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": global_batch,
            "train_micro_batch_size_per_gpu": micro_batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2 if n_dev > 1 else 0},
            "activation_checkpointing": {"enabled": True},
        })
    del params

    def step(batch):
        t = time.perf_counter()
        loss = float(jax.device_get(engine.train_step([batch])))
        return loss, time.perf_counter() - t

    losses, times = [step(first)[0]], []
    compile_s = time.perf_counter() - t0
    for _ in range(warmup - 1):
        losses.append(step(next(batches))[0])
    fused = engine._get_train_step(engine._module_needs_rng(), len(first))
    sentinel = CompileSentinel(fused, 0, name="fused train_step")
    for _ in range(steps):
        loss, dt = step(next(batches))
        losses.append(loss)
        times.append(dt)
    sentinel.check()

    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    attention = attn.traced_implementation(since=traced_before)
    wanted = "dense" if attn.materialises_scores(
        micro_batch, cfg.num_attention_heads, seq_len, seq_len, True,
        cfg.attention_probs_dropout_prob) else "pallas"
    if native and attention != wanted:
        raise AssertionError(
            f"train step traced attention {attention!r}, wanted {wanted!r}")

    report = {
        "params": n_params, "global_batch": global_batch, "seq_len": seq_len,
        "devices": n_dev, "attention": attention,
        # the gauge keeps the last traced kernels' rows: theirs only if
        # this phase traced kernels
        "attention_rows_per_step": (attn.traced_rows_per_step()
                                    if attention == "pallas" else 0),
        "losses": [round(x, 4) for x in losses],
        "init_and_first_step_s": round(compile_s, 1),
        "step_ms": [round(t * 1e3, 1) for t in times],
        "compiles_after_warmup": sentinel.compiles,
    }
    mem = _memory_stats()
    if mem is not None:
        report["peak_bytes_in_use"] = [m["peak_bytes_in_use"] for m in mem]
        report["bytes_in_use"] = [m["bytes_in_use"] for m in mem]
    if n_dev > 1:
        _check_zero_sharding(engine, n_dev, report)
    return report


def _check_zero_sharding(engine, n_dev, report):
    """ZeRO-2's flat fp32 master and Adam moments must each live as one
    equal slice per device, and no device may carry several times the
    memory of another (state piled on device 0)."""
    import jax

    state = engine.opt_state
    big = [x for x in jax.tree_util.tree_leaves(state)
           if getattr(x, "ndim", 0) == 1 and x.size >= n_dev]
    master = state.flat_master
    if master.size == 0 or not any(x is master for x in big):
        raise AssertionError("ZeRO state holds no flat fp32 master")
    for x in big:
        shards = x.addressable_shards
        devices = {s.device for s in shards}
        sizes = {s.data.shape for s in shards}
        if len(devices) != n_dev or sizes != {(x.size // n_dev,)}:
            raise AssertionError(
                f"ZeRO state of {x.size} elements is not split {n_dev} "
                f"ways: {len(devices)} devices, shard shapes {sizes}")
    report["zero_sharded_vectors"] = len(big)
    if "bytes_in_use" in report:
        used = report["bytes_in_use"]
        if max(used) > 2 * min(used):
            raise AssertionError(f"device memory is uneven: {used}")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _global_matmul_precision(value):
    """Process-wide (the serving loop traces on its own thread, which a
    thread-local ``jax.default_matmul_precision`` block would not reach)."""
    import jax

    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", value)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def serve_phase(cfg, *, mesh_shape=None, **kwargs):
    """GPT-2 serving through ``ServingEngine``; see ``_serve_phase`` for the
    arguments and checks.

    On a mesh the phase runs at full f32 matmul precision. XLA partitions
    the engine's batched programs and ``generate()``'s batch-1 program
    differently (an all-reduce here, a reduce-scatter and all-gather there),
    so the partial sums of a row-parallel matmul meet in another order. At
    the TPU's default precision (bf16 operands) that last-bit noise is
    amplified layer by layer until it tips the argmax between near-tied
    logits of a random-weight model: on the first four-chip run 7 of 46
    oracle tokens, while sharded and single-device ``generate()`` agreed on
    all 46. At full precision it stays rounding noise and the token-for-token
    oracle means something. One device needs none of this: same reduction
    order, equal bits."""
    if mesh_shape is None:
        return _serve_phase(cfg, mesh_shape=None, **kwargs)
    with _global_matmul_precision("highest"):
        return _serve_phase(cfg, mesh_shape=mesh_shape, **kwargs)


def _serve_phase(cfg, *, max_seq_len, prompt_buckets, max_slots, requests,
                 mesh_shape, oracles=2, seed=0, timeout_s=600.0):
    """``requests`` is a list of
    (prompt_length, max_new_tokens). Raises unless every request returns
    exactly the tokens it asked for, the first ``oracles`` of them equal
    one-shot ``generate()`` token for token, and the decode program
    compiled once; on a mesh also unless each device holds its share of
    the KV pool and the sharded model agrees with the single-device one."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import generate
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.inference.serving.families import gpt2 as gpt2_mod
    from deepspeed_tpu.models.gpt2 import init_gpt2
    from deepspeed_tpu.profiling.sentinels import CompileSentinel

    t0 = time.perf_counter()
    # traced as one program, for the same reason as the trainer's init
    params = jax.jit(lambda: init_gpt2(
        cfg, batch_size=1, seq_len=min(128, max_seq_len), seed=seed)[1])()
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n, _ in requests]

    decode_sentinel = CompileSentinel(gpt2_mod._decode_step_jit, 1,
                                      name="serving decode step")
    engine = ServingEngine(params, cfg, ServingConfig(
        max_slots=max_slots, max_queue=max(len(requests), 1),
        max_seq_len=max_seq_len, prompt_buckets=tuple(prompt_buckets),
        mesh_shape=mesh_shape))
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.start()
    try:
        futures = [engine.submit(p, max_new_tokens=n)
                   for p, (_, n) in zip(prompts, requests)]
        outputs = [list(f.result(timeout=timeout_s)) for f in futures]
    finally:
        engine.stop()
    serve_s = time.perf_counter() - t0

    for i, (out, (_, want_n)) in enumerate(zip(outputs, requests)):
        if len(out) != want_n:
            raise AssertionError(
                f"request {i} returned {len(out)} tokens, asked {want_n}")
        if not all(0 <= t < cfg.vocab_size for t in out):
            raise AssertionError(f"request {i} returned out-of-vocab tokens")
    decode_sentinel.check()
    if decode_sentinel.compiles != 1:
        raise AssertionError(
            f"decode step compiled {decode_sentinel.compiles} times")

    # The oracle is one-shot generate() on the weights AS THE ENGINE HOLDS
    # THEM (on a mesh: sharded), token for token. The single-device run is a
    # second opinion on the sharding itself, held to a coarser standard: a
    # wrong sharding, the thing it guards, scrambles every token.
    t0 = time.perf_counter()
    differs = []
    one_device_agree, one_device_total = 0, 0
    for i in range(oracles):
        ids = jnp.asarray([prompts[i]], jnp.int32)
        want = np.asarray(generate(engine.params, cfg, ids,
                                   requests[i][1]))[0].tolist()
        if outputs[i] != want:
            differs.append(
                f"request {i} (prompt {requests[i][0]} tokens): served "
                f"{outputs[i]}, one-shot {want}")
        if mesh_shape is not None:
            single = np.asarray(generate(params, cfg, ids,
                                         requests[i][1]))[0].tolist()
            one_device_agree += sum(a == b for a, b in zip(want, single))
            one_device_total += len(single)
    oracle_s = time.perf_counter() - t0
    agreement = f"{one_device_agree}/{one_device_total}"
    if differs:
        raise AssertionError(
            "served tokens differ from generate() (single-device agreement "
            f"{agreement}): " + "; ".join(differs))
    if one_device_agree < 0.75 * one_device_total:
        raise AssertionError(
            f"sharded and single-device generate() agree on only "
            f"{agreement} tokens")

    report = {
        "params": n_params, "requests": len(requests),
        "tokens_out": sum(len(o) for o in outputs),
        "oracle_matches": oracles, "decode_compiles": decode_sentinel.compiles,
        "mesh_shape": mesh_shape, "kv_pool_bytes": engine.pool.nbytes(),
        "init_s": round(init_s, 1), "serve_s": round(serve_s, 1),
        "oracle_s": round(oracle_s, 1),
    }
    if mesh_shape is not None:
        report["one_device_token_agreement"] = agreement
        per_device = {}
        for arr in (engine.pool.k, engine.pool.v):
            for s in arr.addressable_shards:
                per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                           + s.data.nbytes)
        share = engine.pool.nbytes() // mesh_shape[1]
        report["kv_pool_bytes_per_device"] = sorted(per_device.values())
        n_mesh = mesh_shape[0] * mesh_shape[1]
        if (len(per_device) != n_mesh
                or set(per_device.values()) != {share}):
            raise AssertionError(
                f"KV pool is not split over the model axis: per-device bytes "
                f"{per_device}, expected {share} on each of {n_mesh}")
    mem = _memory_stats()
    if mem is not None:
        report["peak_bytes_in_use"] = [m["peak_bytes_in_use"] for m in mem]
    return report


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _release(what):
    """Drop everything the previous phase left on the device: BERT-large's
    training state and a GPT-2 pool do not fit 16 GB together."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
    mem = _memory_stats()
    if mem is not None:
        _say(f"after {what}: bytes_in_use "
             f"{[m['bytes_in_use'] for m in mem]}")


def main():
    t_start = time.perf_counter()
    import jax

    device = device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {device}",
              file=sys.stderr)
        return 1

    import deepspeed_tpu
    from deepspeed_tpu.models.bert import BertConfig
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.ops.op_builder import load_host_library
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger

    # the engine's INFO-level config dump would bury the phase reports in
    # the tail of stdout a remote runner shows; warnings still print
    logger.setLevel(logging.WARNING)
    cache_dir = enable_compile_cache()
    import flax
    import jaxlib
    import optax

    _say(f"device {json.dumps(device)}")
    _say(f"python {sys.version.split()[0]} jax {jax.__version__} "
         f"jaxlib {jaxlib.__version__} flax {flax.__version__} "
         f"optax {optax.__version__} deepspeed_tpu {deepspeed_tpu.__version__}")
    _say(f"compile cache: {cache_dir}")
    _say(f"native host library built here: {load_host_library() is not None}")
    n_dev = device["count"]

    t0 = time.perf_counter()
    bert = BertConfig.bert_large(checkpoint_policy="dots")
    gpt2 = GPT2Config.gpt2_large()
    # BERT-large's own attention call (seq 128, micro-batch 64) holds 64 MiB
    # of float32 scores and takes the materialised path when dropout is off;
    # the kernels are checked at twice that batch and at seq 512
    report = kernel_phase(
        flash_shapes=[(128, bert.num_attention_heads, 128, 64),
                      (16, bert.num_attention_heads, 512, 64)],
        dense_shape=(64, bert.num_attention_heads, 128, 64),
        heads=gpt2.num_attention_heads,
        head_dim=gpt2.hidden_size // gpt2.num_attention_heads,
        page_tokens=128, native=True)
    _say(f"kernels ok in {time.perf_counter() - t0:.1f}s {json.dumps(report)}")
    _release("kernels")

    t0 = time.perf_counter()
    report = train_phase(bert, seq_len=128, micro_batch=64, warmup=3, steps=5,
                         native=True)
    _say(f"train ok in {time.perf_counter() - t0:.1f}s {json.dumps(report)}")
    _release("train")

    t0 = time.perf_counter()
    report = serve_phase(
        gpt2, max_seq_len=1024, prompt_buckets=(32, 128), max_slots=4,
        requests=[(7, 12), (100, 16), (19, 8), (64, 10), (3, 16), (128, 6),
                  (31, 9), (90, 14)],
        mesh_shape=(1, n_dev) if n_dev > 1 else None)
    _say(f"serve ok in {time.perf_counter() - t0:.1f}s {json.dumps(report)}")

    _say(f"all phases ok in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
