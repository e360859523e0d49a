"""Continuous-batching serving engine (inference/serving/).

The load-bearing property is the BITWISE oracle: continuous-batched
greedy output equals per-request one-shot ``generate()`` output for any
arrival order — admission mid-decode, retirement, and slot reuse must be
numerically invisible to every other request. The recompile pins assert
the performance contract that makes continuous batching viable on XLA:
slot churn never recompiles the decode step, and prefill compiles are
bounded by the prompt-length bucket ladder.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import generate
from deepspeed_tpu.inference.serving import (
    ContinuousBatchingScheduler,
    KVCachePool,
    PoolExhaustedError,
    QueueFullError,
    RequestTimeoutError,
    ServingConfig,
    ServingEngine,
    ServingFaultInjector,
    bucket_for,
    default_buckets,
)
from deepspeed_tpu.inference.serving.families import gpt2 as serving_engine_mod
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from deepspeed_tpu.profiling import CompileSentinel, transfer_free
from deepspeed_tpu.profiling.config import DeepSpeedSentinelConfig


def _tiny_config():
    return GPT2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


@pytest.fixture(autouse=True, scope="module")
def _drop_jit_caches():
    # Oracle replays compile per-engine prefill/decode programs; drop
    # them once the module is done so later suite compiles stay fast.
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_config()
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


def _engine(cfg, params, sentinel_config=None, **overrides):
    kw = dict(max_slots=3, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8))
    kw.update(overrides)
    return ServingEngine(params, cfg, ServingConfig(**kw),
                         sentinel_config=sentinel_config)


def _decode_sentinel(budget):
    return CompileSentinel(serving_engine_mod._decode_step_jit, budget,
                           name="decode step")


def _prefill_sentinel(budget):
    return CompileSentinel(serving_engine_mod._prefill_batch_jit, budget,
                           name="batched prefill")


def _prompts(n, lengths=(4, 6, 3, 5, 8, 2, 7, 4)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 64, (lengths[i % len(lengths)],)).tolist()
            for i in range(n)]


def _oneshot(cfg, params, prompt, n_new):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(out)[0].tolist()


# -- the bitwise oracle under three arrival schedules -----------------------

def test_oracle_all_upfront_with_queueing(model):
    """Schedule 1: every request submitted before the first step; more
    requests than slots, so the tail waits in the queue and reuses
    retired slots."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2)
    prompts = _prompts(5)
    wants = [_oneshot(cfg, params, p, 6) for p in prompts]

    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=200)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    occ = eng.occupancy()
    assert occ["in_use"] == 0 and occ["allocations"] == 5 and occ["frees"] == 5
    assert occ["peak_in_use"] <= 2


def test_oracle_mid_decode_admission(model):
    """Schedule 2: a wave of requests joins while the first wave is
    mid-decode — the joiners must not perturb in-flight lanes and must
    themselves decode bitwise-correctly from a partially-filled pool."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompts = _prompts(5)
    wants = [_oneshot(cfg, params, p, 6) for p in prompts]

    futs = [eng.submit(p, max_new_tokens=6) for p in prompts[:3]]
    eng.step()
    eng.step()
    assert any(not f.done() for f in futs)      # genuinely mid-decode
    futs += [eng.submit(p, max_new_tokens=6) for p in prompts[3:]]
    eng.drain(max_steps=200)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_oracle_staggered_lengths_and_slot_reuse(model):
    """Schedule 3: mixed max_new_tokens so requests retire at different
    steps; late arrivals land in freed slots whose cache still holds the
    previous occupant's (stale) keys/values."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2)
    prompts = _prompts(6)
    lens = [2, 7, 4, 3, 6, 5]
    wants = [_oneshot(cfg, params, p, n) for p, n in zip(prompts, lens)]

    futs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts[:2], lens[:2])]
    eng.step()                                   # req0 (2 tokens) retires fast
    futs.append(eng.submit(prompts[2], max_new_tokens=lens[2]))
    eng.step()
    eng.step()
    futs += [eng.submit(p, max_new_tokens=n)
             for p, n in zip(prompts[3:], lens[3:])]
    eng.drain(max_steps=200)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    occ = eng.occupancy()
    assert occ["allocations"] == 6 and occ["peak_in_use"] <= 2


def test_eos_retires_early(model):
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = _prompts(1)[0]
    want = _oneshot(cfg, params, prompt, 8)
    eos = want[3]
    cut = want.index(eos)                        # first occurrence wins

    got = eng.submit(prompt, max_new_tokens=8, eos_token_id=eos)
    eng.drain(max_steps=100)
    assert got.result(timeout=1) == want[:cut + 1]
    assert eng.occupancy()["in_use"] == 0


def test_streaming_callback_sees_every_token(model):
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = _prompts(1)[0]
    seen = []
    fut = eng.submit(prompt, max_new_tokens=5,
                     stream_cb=lambda rid, tok: seen.append((rid, tok)))
    eng.drain(max_steps=100)
    final = fut.result(timeout=1)
    assert [t for _, t in seen] == final == _oneshot(cfg, params, prompt, 5)
    assert len({rid for rid, _ in seen}) == 1


# -- backpressure and deadlines ---------------------------------------------

def test_queue_backpressure(model):
    cfg, params = model
    eng = _engine(cfg, params, max_queue=2)
    prompts = _prompts(3)
    futs = [eng.submit(p, max_new_tokens=2) for p in prompts[:2]]
    with pytest.raises(QueueFullError):
        eng.submit(prompts[2], max_new_tokens=2)
    eng.drain(max_steps=100)                     # shed load -> queue drains
    for f, p in zip(futs, prompts):
        assert f.result(timeout=1) == _oneshot(cfg, params, p, 2)
    eng.submit(prompts[2], max_new_tokens=2)     # capacity is back


def test_deadline_mid_decode(model):
    cfg, params = model
    eng = _engine(cfg, params)
    prompts = _prompts(2)
    doomed = eng.submit(prompts[0], max_new_tokens=8, timeout_s=60.0)
    healthy = eng.submit(prompts[1], max_new_tokens=4)
    eng.step()                                   # both admitted, 1 token out
    assert not doomed.done()
    # shrink the in-flight deadline so the NEXT step reaps it mid-decode
    # (a submit-time micro-deadline would expire while still queued)
    next(r for r in eng.lanes.requests.values()
         if r.future is doomed).timeout_s = 1e-6
    eng.drain(max_steps=100)

    with pytest.raises(RequestTimeoutError) as ei:
        doomed.result(timeout=1)
    assert ei.value.phase == "decoding" and ei.value.tokens_done >= 1
    assert healthy.result(timeout=1) == _oneshot(cfg, params, prompts[1], 4)
    assert eng.occupancy()["in_use"] == 0        # the slot was reclaimed


def test_deadline_while_queued(model):
    cfg, params = model
    eng = _engine(cfg, params, max_slots=1)
    prompts = _prompts(2)
    hog = eng.submit(prompts[0], max_new_tokens=6)
    doomed = eng.submit(prompts[1], max_new_tokens=6, timeout_s=1e-6)
    eng.drain(max_steps=100)

    with pytest.raises(RequestTimeoutError) as ei:
        doomed.result(timeout=1)
    assert ei.value.phase == "queued" and ei.value.tokens_done == 0
    assert hog.result(timeout=1) == _oneshot(cfg, params, prompts[0], 6)


def test_submit_validation(model):
    cfg, params = model
    eng = _engine(cfg, params)
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=2)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError):
        eng.submit(list(range(9)), max_new_tokens=2)   # beyond largest bucket
    with pytest.raises(ValueError):
        eng.submit(list(range(8)), max_new_tokens=30)  # blows max_seq_len
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=2, eos_token_id=64)


# -- the recompile pins -----------------------------------------------------

def test_recompile_pin_over_slot_churn(model):
    """A full serve of 2x MaxSlots requests spanning every bucket: the
    decode step compiles at most once, prefill at most once per bucket —
    CompileSentinel budgets pin it (check() raises past the budget)."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2)
    decode_sent = _decode_sentinel(budget=1)
    prefill_sent = _prefill_sentinel(budget=2)   # |buckets|

    prompts = _prompts(4, lengths=(3, 6, 4, 8))  # buckets 4,8,4,8
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
    eng.step()
    futs += [eng.submit(p, max_new_tokens=5) for p in prompts[2:]]
    eng.drain(max_steps=200)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert decode_sent.check() <= 1
    assert prefill_sent.check() <= 2


def test_steady_state_decode_is_transfer_free(model):
    """The serving contract the lane-state refactor buys: once lanes are
    admitted, decode steps perform ZERO implicit host<->device transfers
    — the lane vectors live on device, positions advance inside the jit,
    and the only per-step host contact is the explicit EOS read. The
    transfer guard raises on any regression (a numpy operand sneaking
    into the jitted call, a float()/.item() on a device value)."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompts = _prompts(2, lengths=(3, 4))
    wants = [_oneshot(cfg, params, p, 8) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()             # admission: prefill + lane-churn upload queued
    eng.step()             # flushes the churn upload (explicit device_put)
    assert eng.lanes.dirty is False and len(eng.lanes.requests) == 2
    with transfer_free():
        for _ in range(4):  # steady state: no admission, no retirement
            stats = eng.step()
            assert stats["decoded"] == 2
    eng.drain(max_steps=100)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_armed_sentinels_via_config(model):
    """jax_sentinels wiring: an engine built with the block enabled
    checks its own compile budgets and runs decode under the transfer
    guard — and still serves bitwise-correct output."""
    cfg, params = model
    sent_cfg = DeepSpeedSentinelConfig({"jax_sentinels": {
        "enabled": True, "compile_budget": 8, "transfer_guard": True}})
    eng = _engine(cfg, params, sentinel_config=sent_cfg)
    assert eng.decode_sentinel is not None
    assert eng.prefill_sentinel is not None and eng._transfer_guard
    prompts = _prompts(3)
    wants = [_oneshot(cfg, params, p, 4) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert eng.decode_sentinel.check() <= 8


# -- fault injection --------------------------------------------------------

@pytest.mark.faults
def test_stuck_request_reaped_and_slot_reused(model):
    """stuck_request suppresses natural retirement; only the deadline can
    reap it. Neighbors must finish bitwise-correct and the reclaimed slot
    must serve a fresh request."""
    cfg, params = model
    fi = ServingFaultInjector()
    fi.arm_serving("stuck_request", request_id=0)
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8)),
        injector=fi)
    prompts = _prompts(3)

    stuck = eng.submit(prompts[0], max_new_tokens=2, timeout_s=0.3)
    healthy = eng.submit(prompts[1], max_new_tokens=6)
    eng.drain(max_steps=5000)

    with pytest.raises(RequestTimeoutError) as ei:
        stuck.result(timeout=1)
    assert ei.value.phase == "decoding"
    assert ei.value.tokens_done > 2              # decoded PAST max_new_tokens
    assert fi.fired["stuck_request"] >= 1
    assert healthy.result(timeout=1) == _oneshot(cfg, params, prompts[1], 6)
    assert eng.occupancy()["in_use"] == 0

    after = eng.submit(prompts[2], max_new_tokens=3)   # reuse the freed slot
    eng.drain(max_steps=100)
    assert after.result(timeout=1) == _oneshot(cfg, params, prompts[2], 3)


@pytest.mark.faults
def test_slow_decode_arm_delays_but_preserves_output(model):
    cfg, params = model
    fi = ServingFaultInjector({"slow_decode": {"at_step": 0, "seconds": 0.05,
                                               "times": 1}})
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8)),
        injector=fi)
    prompt = _prompts(1)[0]
    t0 = time.monotonic()
    fut = eng.submit(prompt, max_new_tokens=3)
    eng.drain(max_steps=100)
    assert time.monotonic() - t0 >= 0.05
    assert fi.fired["slow_decode"] == 1
    assert fut.result(timeout=1) == _oneshot(cfg, params, prompt, 3)


def test_fault_injection_via_config(model):
    """The serving config block's fault_injection spec builds the
    injector (same spec-driven path the checkpoint/step injectors use)."""
    cfg, params = model
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, max_queue=4, max_seq_len=32, prompt_buckets=(4, 8),
        fault_injection={"slow_decode": {"at_step": 0, "seconds": 0.0}}))
    assert isinstance(eng.injector, ServingFaultInjector)
    fut = eng.submit(_prompts(1)[0], max_new_tokens=2)
    eng.drain(max_steps=100)
    assert fut.result(timeout=1)
    assert eng.injector.fired["slow_decode"] >= 1


# -- pool and scheduler units -----------------------------------------------

def test_kv_pool_allocate_free_lifecycle():
    pool = KVCachePool(n_layers=2, max_slots=2, n_heads=4, max_seq_len=16,
                       head_dim=8)
    a, b = pool.allocate(), pool.allocate()
    assert {a, b} == {0, 1}
    with pytest.raises(PoolExhaustedError):
        pool.allocate()
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)                             # double free
    assert pool.allocate() == a                  # lowest-first determinism
    occ = pool.occupancy()
    assert occ["max_slots"] == 2 and occ["in_use"] == 2
    assert occ["allocations"] == 3 and occ["frees"] == 1
    assert occ["peak_in_use"] == 2 and occ["utilization"] == 1.0


def test_scheduler_bucketing_and_retirement():
    assert default_buckets(31) == (8, 16, 31)
    assert default_buckets(8) == (8,)
    assert bucket_for(5, (4, 8)) == 8 and bucket_for(4, (4, 8)) == 4
    with pytest.raises(ValueError):
        bucket_for(9, (4, 8))
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(max_queue=2, buckets=(8, 4))
    sched = ContinuousBatchingScheduler(max_queue=1, buckets=(8,))
    req = sched.submit([1, 2], max_new_tokens=3, eos_token_id=5)
    with pytest.raises(QueueFullError):
        sched.submit([3], max_new_tokens=1)
    req.emitted = 1
    assert sched.should_retire(req, 5) == "eos"
    assert sched.should_retire(req, 4) is None
    assert sched.should_retire(req, 5, stuck=True) is None
    req.emitted = 3
    assert sched.should_retire(req, 4) == "length"


# -- config plumbing --------------------------------------------------------

def test_serving_config_block_validated():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    base = {"train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 1}
    off = DeepSpeedConfig(dict(base), world_size=1)
    assert off.serving_config.enabled is False

    on = DeepSpeedConfig(
        dict(base, serving={"max_slots": 4, "prompt_buckets": [4, 8],
                            "request_timeout_s": 1.5}), world_size=1)
    sc = on.serving_config
    assert sc.enabled and sc.max_slots == 4
    assert sc.prompt_buckets == (4, 8) and sc.request_timeout_s == 1.5
    assert sc.max_queue == 64 and sc.default_max_new_tokens == 64

    for bad in ({"max_slots": 0}, {"max_queue": 0}, {"max_seq_len": 1},
                {"prompt_buckets": [8, 4]}, {"prompt_buckets": [4, 4]},
                {"default_max_new_tokens": 0}, {"request_timeout_s": -1},
                {"fault_injection": "nope"}):
        with pytest.raises(ValueError):
            DeepSpeedConfig(dict(base, serving=bad), world_size=1)


def test_from_config_builds_engine_with_monitor(model, tmpdir):
    cfg, params = model
    out = str(tmpdir.join("csv"))
    ds = {"train_micro_batch_size_per_gpu": 1,
          "gradient_accumulation_steps": 1,
          "serving": {"max_slots": 2, "prompt_buckets": [4, 8],
                      "max_seq_len": 32},
          "csv_monitor": {"enabled": True, "output_path": out,
                          "job_name": "serve"}}
    eng = ServingEngine.from_config(params, cfg, ds)
    prompt = _prompts(1)[0]
    fut = eng.submit(prompt, max_new_tokens=3)
    eng.drain(max_steps=100)
    assert fut.result(timeout=1) == _oneshot(cfg, params, prompt, 3)
    eng.close()                                  # flushes the monitor
    written = os.listdir(os.path.join(out, "serve"))
    assert any(f.startswith("Serving_") for f in written)


def test_engine_rejects_bad_geometry(model):
    cfg, params = model
    with pytest.raises(ValueError):              # > max_position_embeddings
        _engine(cfg, params, max_seq_len=64)
    with pytest.raises(ValueError):              # bucket leaves no decode room
        _engine(cfg, params, max_seq_len=8, prompt_buckets=(8,))


# -- background-thread mode -------------------------------------------------

def test_background_loop_serves_from_another_thread(model):
    cfg, params = model
    eng = _engine(cfg, params)
    prompts = _prompts(3)
    eng.start(idle_sleep_s=0.001)
    try:
        futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        for f, p in zip(futs, prompts):
            assert f.result(timeout=10) == _oneshot(cfg, params, p, 4)
    finally:
        eng.stop()
    assert eng.occupancy()["in_use"] == 0


def test_metrics_snapshot(model):
    cfg, params = model
    eng = _engine(cfg, params)
    futs = [eng.submit(p, max_new_tokens=4) for p in _prompts(2)]
    eng.drain(max_steps=100)
    for f in futs:
        f.result(timeout=1)
    snap = eng.metrics.snapshot()
    assert snap["requests_completed"] == 2 and snap["requests_timed_out"] == 0
    assert snap["avg_ttft_s"] > 0 and snap["tokens_per_sec"] > 0
    assert snap["decode_steps"] > 0 and snap["tokens_emitted"] >= 6


# -- batched prefill admission ----------------------------------------------

def test_batched_admission_one_prefill_call(model):
    """Same-bucket requests queued together prefill as ONE call: the
    whole group shares a single [MaxSlots, Sb] forward."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=3)
    prompts = _prompts(3, lengths=(3, 4, 2))     # all bucket 4
    wants = [_oneshot(cfg, params, p, 4) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.step()                                   # one admission pass
    assert eng.metrics.prefill_calls == 1        # grouped, not per-request
    eng.drain(max_steps=100)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_recompile_pin_varying_group_size(model):
    """The prefill batch dimension is padded to the static MaxSlots:
    admission groups of 1, 2, and 3 same-bucket requests must all share
    one compiled program."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=3)
    prefill_sent = _prefill_sentinel(budget=1)
    for group in (1, 3, 2):
        prompts = _prompts(group, lengths=(3, 4, 2))
        wants = [_oneshot(cfg, params, p, 3) for p in prompts]
        futs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.drain(max_steps=100)
        prefill_sent.check()     # raises on the offending group size
        for f, want in zip(futs, wants):
            assert f.result(timeout=1) == want
    assert prefill_sent.check() <= 1


# -- chunked prefill --------------------------------------------------------

def test_chunked_prefill_oracle_and_interleaving(model):
    """A long prompt prefills in chunks interleaved with decode steps:
    the in-flight short request keeps emitting tokens while the long
    prompt progresses, and both finish bitwise-correct."""
    cfg, params = model
    eng = _engine(cfg, params, prefill_chunk_tokens=3)
    short, long_p = _prompts(2, lengths=(3, 8))
    want_short = _oneshot(cfg, params, short, 8)
    want_long = _oneshot(cfg, params, long_p, 4)

    f_short = eng.submit(short, max_new_tokens=8)
    eng.step()                                   # short admitted, decoding
    f_long = eng.submit(long_p, max_new_tokens=4)
    chunk_steps = decode_during_chunks = 0
    while not f_long.done():
        stats = eng.step()
        if stats["prefill_chunks"]:
            chunk_steps += stats["prefill_chunks"]
            decode_during_chunks += stats["decoded"]
        assert stats["prefill_chunks"] <= 1      # one chunk per step
    eng.drain(max_steps=100)
    assert chunk_steps == 3                      # ceil(8 / 3)
    assert decode_during_chunks >= 1             # decode ran BETWEEN chunks
    assert f_short.result(timeout=1) == want_short
    assert f_long.result(timeout=1) == want_long


def test_chunked_prefill_compile_bounded(model):
    """Chunked prefill adds at most ONE compiled program (B=1, Sb=chunk)
    regardless of how many long prompts stream through."""
    cfg, params = model
    eng = _engine(cfg, params, prefill_chunk_tokens=3)
    prefill_sent = _prefill_sentinel(budget=1)
    for p in _prompts(3, lengths=(8, 7, 8)):
        fut = eng.submit(p, max_new_tokens=3)
        eng.drain(max_steps=100)
        assert fut.result(timeout=1) == _oneshot(cfg, params, p, 3)
    assert prefill_sent.check() <= 1


def test_chunked_prefill_deadline_aborts_with_prefill_phase(model):
    cfg, params = model
    eng = _engine(cfg, params, prefill_chunk_tokens=2)
    doomed = eng.submit(_prompts(1, lengths=(8,))[0], max_new_tokens=4,
                        timeout_s=60.0)
    eng.step()                                   # chunked prefill started
    assert eng.family._chunking is not None
    eng.family._chunking.req.timeout_s = 1e-6           # expire it mid-prefill
    eng.drain(max_steps=100)
    with pytest.raises(RequestTimeoutError) as ei:
        doomed.result(timeout=1)
    assert ei.value.phase == "prefill" and ei.value.tokens_done == 0
    assert eng.occupancy()["in_use"] == 0        # reserved slot reclaimed


# -- prefix KV cache --------------------------------------------------------

def _shared_prefix_prompts(n, prefix_len=5):
    rng = np.random.RandomState(11)
    prefix = rng.randint(0, 64, (prefix_len,)).tolist()
    return [prefix + rng.randint(0, 64, (1 + i % 3,)).tolist()
            for i in range(n)]


@pytest.mark.parametrize("schedule", ["upfront", "mid_decode", "staggered"])
def test_oracle_with_prefix_cache(model, schedule):
    """The bitwise oracle holds with the prefix cache ON, under every
    arrival schedule: seeding KV from a stored prefix must be invisible
    to the emitted tokens."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2, prefix_cache_mb=4.0)
    prompts = _shared_prefix_prompts(5)
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]

    if schedule == "upfront":
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    elif schedule == "mid_decode":
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
        eng.step()
        eng.step()
        futs += [eng.submit(p, max_new_tokens=5) for p in prompts[2:]]
    else:                                        # staggered retirement
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
        eng.drain(max_steps=100)                 # retire the first wave
        futs += [eng.submit(p, max_new_tokens=5) for p in prompts[2:]]
    eng.drain(max_steps=200)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    stats = eng.prefix_stats()
    assert stats["hits"] >= 1                    # later prompts reused KV
    assert stats["referenced"] == 0              # every ref released
    assert eng.metrics.prefix_hit_rate() > 0


def test_prefix_cache_recompile_pin(model):
    """Prefix-cache hits reuse the SAME compiled prefill program: the
    seeded cache and per-lane start offsets are traced operands."""
    cfg, params = model
    eng = _engine(cfg, params, prefix_cache_mb=4.0)
    prefill_sent = _prefill_sentinel(budget=2)   # |buckets|
    prompts = _shared_prefix_prompts(4)
    for p in prompts:                            # serial: every later one hits
        fut = eng.submit(p, max_new_tokens=3)
        eng.drain(max_steps=100)
        assert fut.result(timeout=1) == _oneshot(cfg, params, p, 3)
    assert eng.prefix_stats()["hits"] >= 2
    assert prefill_sent.check() <= 2


def test_prefix_refs_released_after_stuck_reap(model):
    """A stuck request holding a prefix-cache ref is reaped by its
    deadline; the reap must release the ref (no leak after drain)."""
    cfg, params = model
    fi = ServingFaultInjector()
    fi.arm_serving("stuck_request", request_id=1)
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8),
        prefix_cache_mb=4.0), injector=fi)
    prompts = _shared_prefix_prompts(2)
    seed = eng.submit(prompts[0], max_new_tokens=2)          # id 0: inserts
    eng.drain(max_steps=100)
    seed.result(timeout=1)
    stuck = eng.submit(prompts[1], max_new_tokens=2, timeout_s=0.3)  # id 1: hits
    eng.drain(max_steps=5000)
    with pytest.raises(RequestTimeoutError):
        stuck.result(timeout=1)
    assert eng.prefix_stats()["hits"] >= 1
    assert eng.prefix_stats()["referenced"] == 0             # ref released
    assert eng.occupancy()["in_use"] == 0


@pytest.mark.faults
def test_evict_under_decode_preserves_output(model):
    """The evict_under_decode arm drops every unreferenced prefix entry
    mid-serve: in-flight lanes already copied their KV, so outputs stay
    bitwise-correct and later admissions simply miss."""
    cfg, params = model
    fi = ServingFaultInjector({"evict_under_decode": {"at_step": 1}})
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8),
        prefix_cache_mb=4.0), injector=fi)
    prompts = _shared_prefix_prompts(3)
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert fi.fired["evict_under_decode"] >= 1
    assert eng.prefix_stats()["evictions"] >= 1


# -- new config keys --------------------------------------------------------

def test_prefill_config_block_validated():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    base = {"train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 1}
    on = DeepSpeedConfig(
        dict(base, serving={"prefill_chunk_tokens": 16,
                            "prefix_cache_mb": 2.5}), world_size=1)
    assert on.serving_config.prefill_chunk_tokens == 16
    assert on.serving_config.prefix_cache_mb == 2.5
    off = DeepSpeedConfig(dict(base, serving={}), world_size=1)
    assert off.serving_config.prefill_chunk_tokens == 0
    assert off.serving_config.prefix_cache_mb == 0.0
    for bad in ({"prefill_chunk_tokens": -1}, {"prefill_chunk_tokens": 2.5},
                {"prefix_cache_mb": -0.5}, {"prefix_cache_mb": "big"}):
        with pytest.raises(ValueError):
            DeepSpeedConfig(dict(base, serving=bad), world_size=1)


def test_engine_rejects_bad_prefill_config(model):
    cfg, params = model
    with pytest.raises(ValueError):
        _engine(cfg, params, prefill_chunk_tokens=-1)
    with pytest.raises(ValueError):
        _engine(cfg, params, prefix_cache_mb=-1.0)
    assert _engine(cfg, params).prefix_cache is None         # 0 = disabled
    assert _engine(cfg, params).prefix_stats() is None


def test_metrics_snapshot_prefill_keys(model):
    cfg, params = model
    eng = _engine(cfg, params, prefix_cache_mb=4.0)
    prompts = _shared_prefix_prompts(3)
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain(max_steps=100)
    for f in futs:
        f.result(timeout=1)
    snap = eng.metrics.snapshot()
    assert snap["ttft_p50_s"] > 0 and snap["ttft_p95_s"] >= snap["ttft_p50_s"]
    assert snap["prefill_tokens"] >= sum(len(p) for p in prompts) - \
        snap["prefix_reused_tokens"]
    assert snap["decode_tokens"] == snap["tokens_emitted"]
    assert snap["prefill_calls"] >= 1
    assert snap["prefill_tokens_per_sec"] > 0
    assert snap["prefix_hit_rate"] is not None


# -- attention backends & paged KV pool --------------------------------------

def _backend_oneshot(cfg, params, prompt, n_new, impl, pt=8):
    """Per-request greedy reference under a specific backend — the
    per-backend oracle the continuous engine must match bitwise."""
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new,
                   attn_impl=impl, kv_page_tokens=pt)
    return np.asarray(out)[0].tolist()


@pytest.mark.parametrize("impl", ["flash", "sparse_xla"])
def test_backend_oracle_uniform(model, impl):
    """The tentpole contract per backend: continuous-batched greedy
    output equals one-shot generate() under the SAME backend, bitwise,
    with queueing and slot churn."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2, attention_impl=impl,
                  kv_page_tokens=8)
    prompts = _prompts(5)
    wants = [_backend_oneshot(cfg, params, p, 6, impl) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert eng.occupancy()["in_use"] == 0


def test_backend_oracle_per_bucket_mixed(model):
    """A {bucket: impl} ladder routes each prompt to its bucket's
    backend; every request must match ITS backend's generate() bitwise
    even while dense and sparse lanes decode in the same step."""
    cfg, params = model
    eng = _engine(cfg, params, kv_page_tokens=8,
                  attention_impl={4: "dense", 8: "sparse_xla"})
    prompts = _prompts(6, lengths=(3, 7, 4, 8, 2, 6))
    impls = [("dense" if bucket_for(len(p), (4, 8)) == 4 else "sparse_xla")
             for p in prompts]
    wants = [_backend_oneshot(cfg, params, p, 5, i)
             for p, i in zip(prompts, impls)]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.drain(max_steps=300)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_backend_oracle_mid_decode_admission_sparse(model):
    """Sparse lanes joining mid-decode must not perturb in-flight sparse
    lanes (the window program is one batched step over all of them)."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8)
    prompts = _prompts(5)
    wants = [_backend_oneshot(cfg, params, p, 6, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts[:3]]
    eng.step()
    eng.step()
    assert any(not f.done() for f in futs)
    futs += [eng.submit(p, max_new_tokens=6) for p in prompts[3:]]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_backend_oracle_chunked_prefill_sparse(model):
    """Chunked prefill under the sparse backend: chunks are padded up to
    whole pages, which must stay invisible to the output."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8, prefill_chunk_tokens=4)
    prompts = _prompts(3, lengths=(7, 8, 6))
    wants = [_backend_oneshot(cfg, params, p, 5, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.drain(max_steps=300)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_backend_oracle_speculative_sparse(model):
    """speculative_k=4 under the sparse backend: the windowed verify
    program must accept/reject drafts exactly like the k=0 oracle."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8, speculative_k=4)
    prompts = _prompts(4)
    wants = [_backend_oneshot(cfg, params, p, 8, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_backend_oracle_int8_threshold(model):
    """int8 KV under the sparse backend: requantization noise breaks
    bitwise equality by design, so parity is threshold-based like the
    dense int8 path."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8, kv_cache_dtype="int8")
    prompts = _prompts(4)
    wants = [_backend_oneshot(cfg, params, p, 6, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=200)
    matches = total = 0
    for f, want in zip(futs, wants):
        got = f.result(timeout=1)
        assert len(got) == len(want)
        matches += sum(g == w for g, w in zip(got, want))
        total += len(want)
    assert matches / total >= 0.9


def test_backend_oracle_prefix_cache_sparse(model):
    """Prefix-cache hits under the sparse backend stay bitwise-invisible
    — entries are tagged by impl so a sparse lane only ever seeds from
    sparse-produced KV."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8, prefix_cache_mb=4.0)
    prompts = _shared_prefix_prompts(4)
    wants = [_backend_oneshot(cfg, params, p, 5, "sparse_xla")
             for p in prompts]
    futs = []
    for p in prompts:                       # serialize to guarantee hits
        futs.append(eng.submit(p, max_new_tokens=5))
        eng.drain(max_steps=100)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert eng.prefix_cache.hits >= 1


def test_prefix_cache_entries_segregated_by_impl():
    """Direct container check: the same token prefix stored under two
    backends is two entries, and lookups never cross impls."""
    from deepspeed_tpu.inference.serving import PrefixKVCache
    c = PrefixKVCache(budget_bytes=1 << 20)
    k = np.zeros((2, 2, 3, 4), np.float32)
    c.insert((1, 2, 3), k, k.copy())                       # dense
    assert c.match((1, 2, 3))[0] == 3
    assert c.match((1, 2, 3), impl="sparse_xla") == (0, None)
    c.insert((1, 2, 3), k.copy(), k.copy(), impl="sparse_xla")
    n, e = c.match((1, 2, 3), impl="sparse_xla")
    assert n == 3 and e.impl == "sparse_xla" and len(c) == 2


def test_backend_and_page_churn_recompile_pin(model):
    """The perf contract: page-table churn (alloc/free reshuffling
    physical pages) and per-bucket backend switching never recompile
    steady-state decode — one compile per decode program CLASS, total."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=2, kv_page_tokens=8,
                  attention_impl={4: "dense", 8: "sparse_xla"})
    full_sent = _decode_sentinel(budget=1)
    win_sent = CompileSentinel(serving_engine_mod._decode_step_window_jit,
                               1, name="window decode step")
    prompts = _prompts(6, lengths=(3, 7, 4, 8, 2, 6))
    impls = [("dense" if bucket_for(len(p), (4, 8)) == 4 else "sparse_xla")
             for p in prompts]
    wants = [_backend_oneshot(cfg, params, p, 4, i)
             for p, i in zip(prompts, impls)]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts[:3]]
    eng.step()
    futs += [eng.submit(p, max_new_tokens=4) for p in prompts[3:]]
    eng.drain(max_steps=300)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert full_sent.check() <= 1
    assert win_sent.check() <= 1


def test_armed_window_sentinels_via_config(model):
    """jax_sentinels wiring for the window programs: an engine with the
    block enabled and a sparse bucket builds the window decode/prefill
    sentinels and serves bitwise under their budgets."""
    cfg, params = model
    sent_cfg = DeepSpeedSentinelConfig({"jax_sentinels": {
        "enabled": True, "compile_budget": 8, "transfer_guard": True}})
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8, sentinel_config=sent_cfg)
    assert eng.family.decode_window_sentinel is not None
    assert eng.family.prefill_window_sentinel is not None
    prompts = _prompts(3)
    wants = [_backend_oneshot(cfg, params, p, 4, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert eng.family.decode_window_sentinel.check() <= 8


def test_steady_state_transfer_free_sparse(model):
    """transfer_free() holds with the sparse backend armed: the window
    gather/scatter runs entirely on device off the uploaded page
    tables."""
    cfg, params = model
    eng = _engine(cfg, params, attention_impl="sparse_xla",
                  kv_page_tokens=8)
    prompts = _prompts(2, lengths=(3, 4))
    wants = [_backend_oneshot(cfg, params, p, 8, "sparse_xla")
             for p in prompts]
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()
    assert eng.lanes.dirty is False and len(eng.lanes.requests) == 2
    with transfer_free():
        for _ in range(4):
            stats = eng.step()
            assert stats["decoded"] == 2
    eng.drain(max_steps=100)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_page_allocator_alloc_free_reuse():
    """Page accounting: partial-lane allocation claims ceil(n/pt) pages,
    free returns them (lowest-first reuse), and the freed lane's table
    row is zeroed so stale mappings can never leak."""
    pool = KVCachePool(n_layers=2, max_slots=4, n_heads=2, max_seq_len=16,
                       head_dim=8, page_tokens=4, pool_tokens=32)
    assert pool.n_data_pages == 8 and pool.pages_per_lane == 4
    a = pool.allocate(6)                                   # 2 pages
    assert pool.pages_in_use == 2 and pool.lane_tokens(a) == 8
    assert list(pool.page_tables[a]) == [1, 2, 0, 0]
    b = pool.allocate()                                    # full lane
    assert pool.pages_in_use == 6 and pool.lane_tokens(b) == 16
    pool.free(a)
    assert pool.pages_in_use == 4
    assert not pool.page_tables[a].any()                   # row zeroed
    c = pool.allocate(16)                                  # reuses 1, 2
    assert 1 in pool.page_tables[c] and 2 in pool.page_tables[c]
    occ = pool.occupancy()
    assert occ["pages_total"] == 8 and occ["pages_in_use"] == 8
    assert occ["peak_pages_in_use"] == 8 and occ["pages_free"] == 0


def test_page_allocator_exhaustion_message():
    """Running out of pages (not slots) raises PoolExhaustedError with
    the page counts in the message, and leaves the pool untouched."""
    pool = KVCachePool(n_layers=2, max_slots=4, n_heads=2, max_seq_len=16,
                       head_dim=8, page_tokens=4, pool_tokens=16)
    assert pool.n_data_pages == 4
    pool.allocate(16)                                      # all 4 pages
    assert not pool.can_allocate(1)
    with pytest.raises(PoolExhaustedError,
                       match=r"need 1 page.*0 of 4 free"):
        pool.allocate(1)
    assert pool.slots_in_use == 1                          # untouched
    pool.free(0)
    assert pool.can_allocate(16)


def test_paged_pool_undercuts_contiguous_footprint():
    """The memory win the paged layout exists for: a sub-contiguous
    pool_tokens budget makes pool bytes strictly smaller than the
    MaxSlots x S_max contiguous layout at equal slot count."""
    pool = KVCachePool(n_layers=2, max_slots=8, n_heads=2,
                       max_seq_len=1024, head_dim=8, page_tokens=128,
                       pool_tokens=2048)
    assert pool.nbytes() < pool.contiguous_equiv_bytes()
    full = KVCachePool(n_layers=2, max_slots=8, n_heads=2,
                       max_seq_len=1024, head_dim=8, page_tokens=128)
    # default budget == contiguous capacity: one extra (null) page only
    assert full.n_data_pages * full.page_tokens == 8 * 1024


def test_page_backpressure_requeues_until_pages_free(model):
    """Admission backpressure on PAGES, not just slots: with budget for
    one in-flight request, the second waits in the queue and is admitted
    (bitwise-correct) after the first retires."""
    cfg, params = model
    eng = _engine(cfg, params, max_slots=3, kv_page_tokens=8,
                  kv_pool_tokens=32)                       # 4 data pages
    prompts = _prompts(2, lengths=(4, 5))
    wants = [_oneshot(cfg, params, p, 13) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=13) for p in prompts]
    eng.drain(max_steps=400)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    occ = eng.occupancy()
    assert occ["in_use"] == 0 and occ["peak_pages_in_use"] <= 4


def test_metrics_pages_and_admitted_histogram(model):
    """Satellite: Serving/pages_in_use + page_fragmentation gauges and
    the per-bucket admitted-prompt-length histogram in snapshot()."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompts = _prompts(3, lengths=(3, 7, 4))
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain(max_steps=100)
    for f in futs:
        f.result(timeout=1)
    snap = eng.metrics.snapshot()
    assert "pages_in_use" in snap and "page_fragmentation" in snap
    assert snap["admitted_prompts_bucket_4"] == 2
    assert snap["admitted_prompts_bucket_8"] == 1
    assert snap["admitted_prompt_len_min_bucket_4"] == 3
    assert snap["admitted_prompt_len_max_bucket_4"] == 4
    assert snap["admitted_prompt_len_mean_bucket_8"] == 7.0
    # numeric keys -> the Prometheus export picks them up unchanged
    from deepspeed_tpu.telemetry import MetricsRegistry
    reg = eng.metrics.export_to(MetricsRegistry())
    text = reg.render_prometheus()
    assert "pages_in_use" in text and "admitted_prompts_bucket_4" in text


def test_engine_rejects_bad_backend_config(model):
    cfg, params = model
    with pytest.raises(ValueError, match="attention_impl"):
        _engine(cfg, params, attention_impl="nope")
    with pytest.raises(ValueError, match="attention_impl"):
        _engine(cfg, params, attention_impl={16: "sparse_xla"})
    with pytest.raises(ValueError, match="kv_page_tokens"):
        _engine(cfg, params, kv_page_tokens=0)
    with pytest.raises(ValueError, match="kv_pool_tokens"):
        _engine(cfg, params, kv_pool_tokens=0)
