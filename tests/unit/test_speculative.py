"""Speculative decoding + quantized KV pool (inference/serving/).

Two contracts layered on the PR 5 serving oracle:

- SPECULATION IS OUTPUT-INVISIBLE: every emitted token comes from the
  verify forward's greedy oracle, so for any ``speculative_k`` the
  served output equals per-request ``generate()`` — drafts (even
  adversarially corrupted ones) only change how many tokens a step
  yields. ``speculative_k=0`` runs the exact pre-existing program and
  stays bitwise by construction.
- QUANTIZED KV IS THRESHOLD-PARITY: int8 storage (per-(slot, head)
  scales, dequant at use) must keep greedy token-match above a
  threshold and attention outputs allclose, while halving/quartering
  the reported pool bytes at equal MaxSlots.

Plus the performance pins that make both viable: acceptance variation,
draft contents, and slot churn never recompile the (static-k) step, and
steady-state speculative decode stays transfer-free.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import generate
from deepspeed_tpu.inference.generation import (
    _forward_chunk,
    _ngram_draft,
)
from deepspeed_tpu.inference.quantization import (
    dequantize_kv,
    quantize_kv,
    quantize_kv_np,
    requantize_kv,
)
from deepspeed_tpu.inference.serving import (
    KVCachePool,
    ServingConfig,
    ServingEngine,
    ServingFaultInjector,
)
from deepspeed_tpu.inference.serving.families import gpt2 as serving_engine_mod
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from deepspeed_tpu.profiling import CompileSentinel, transfer_free
from deepspeed_tpu.runtime.config import get_serving_config

# int8 KV on the tiny model matches fp32 greedy exactly in practice;
# the pinned threshold leaves room for platform-dependent rounding
# without letting real regressions through.
INT8_TOKEN_MATCH_THRESHOLD = 0.9


def _tiny_config():
    return GPT2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


@pytest.fixture(autouse=True, scope="module")
def _drop_jit_caches():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_config()
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


def _engine(cfg, params, sentinel_config=None, injector=None, **overrides):
    kw = dict(max_slots=2, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8))
    kw.update(overrides)
    return ServingEngine(params, cfg, ServingConfig(**kw),
                         sentinel_config=sentinel_config, injector=injector)


def _prompts(n, lengths=(4, 6, 3, 5, 8, 2, 7, 4)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 64, (lengths[i % len(lengths)],)).tolist()
            for i in range(n)]


def _shared_prefix_prompts(n, prefix_len=5):
    rng = np.random.RandomState(11)
    prefix = rng.randint(0, 64, (prefix_len,)).tolist()
    return [prefix + rng.randint(0, 64, (1 + i % 3,)).tolist()
            for i in range(n)]


def _oneshot(cfg, params, prompt, n_new):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(out)[0].tolist()


def _run_schedule(eng, prompts, n_new, schedule):
    if schedule == "upfront":
        futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    elif schedule == "mid_decode":
        futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts[:2]]
        eng.step()
        eng.step()
        futs += [eng.submit(p, max_new_tokens=n_new) for p in prompts[2:]]
    else:                                        # staggered retirement
        futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts[:2]]
        eng.drain(max_steps=200)                 # retire the first wave
        futs += [eng.submit(p, max_new_tokens=n_new) for p in prompts[2:]]
    eng.drain(max_steps=400)
    return futs


# -- speculation is output-identical under every schedule -------------------

@pytest.mark.parametrize("schedule", ["upfront", "mid_decode", "staggered"])
@pytest.mark.parametrize("k", [0, 2, 4])
@pytest.mark.parametrize("prefix", [False, True])
def test_spec_oracle(model, schedule, k, prefix):
    """Served output equals per-request generate() for k in {0, 2, 4},
    under all three arrival schedules, prefix cache on/off. k=0 is the
    pre-existing bitwise program; k>0 must be output-identical because
    emitted tokens always come from the verify oracle."""
    cfg, params = model
    eng = _engine(cfg, params, speculative_k=k,
                  prefix_cache_mb=4.0 if prefix else 0.0)
    prompts = (_shared_prefix_prompts(4) if prefix else _prompts(4))
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]

    futs = _run_schedule(eng, prompts, 5, schedule)

    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    occ = eng.occupancy()
    assert occ["in_use"] == 0
    if k > 0:
        # the drafter must actually have been exercised
        assert eng.metrics.draft_proposed > 0
        assert eng.metrics.tokens_per_step() >= 1.0


def test_spec_with_chunked_prefill(model):
    """Speculation composes with chunked prefill: the history row is
    seeded at activation regardless of how the prompt was prefilled."""
    cfg, params = model
    eng = _engine(cfg, params, speculative_k=2, prefill_chunk_tokens=3)
    prompts = _prompts(3, lengths=(8, 7, 3))
    wants = [_oneshot(cfg, params, p, 6) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=400)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_spec_emits_multiple_tokens_per_step(model):
    """The point of the feature: with drafts accepted, steps emit more
    than one token per lane — tokens_per_step strictly beats the lane
    count and the accept rate is recorded in (0, 1]."""
    cfg, params = model
    eng = _engine(cfg, params, speculative_k=4)
    prompts = _prompts(2, lengths=(3, 4))
    futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.drain(max_steps=200)
    for f in futs:
        assert len(f.result(timeout=1)) == 12
    assert eng.metrics.tokens_per_step() > 1.0   # strictly beat 1 tok/lane
    assert 0.0 < eng.metrics.accept_rate() <= 1.0
    snap = eng.metrics.snapshot()
    assert snap["accept_rate"] == eng.metrics.accept_rate()
    assert snap["tokens_per_step"] == eng.metrics.tokens_per_step()
    assert snap["draft_accepted"] <= snap["draft_proposed"]


# -- performance pins -------------------------------------------------------

def test_spec_recompile_pin_acceptance_and_churn(model):
    """Static-k contract: varying per-lane acceptance counts, varying
    draft contents (including corrupt_draft scrambles), and slot churn
    all reuse ONE compiled speculative step."""
    cfg, params = model
    fi = ServingFaultInjector(
        {"corrupt_draft": {"at_step": 4, "times": 2}})
    eng = _engine(cfg, params, speculative_k=2, injector=fi)
    spec_sent = CompileSentinel(serving_engine_mod._spec_step_jit, 1,
                                name="speculative step")
    prompts = _prompts(5)
    lens = [2, 7, 4, 3, 6]
    wants = [_oneshot(cfg, params, p, n) for p, n in zip(prompts, lens)]
    futs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, lens)]
    eng.drain(max_steps=400)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert fi.fired["corrupt_draft"] >= 1
    assert spec_sent.check() <= 1


def test_spec_steady_state_transfer_free(model):
    """Steady-state speculative decode performs ZERO implicit transfers:
    history/tokens/positions advance in-jit and the only per-step host
    contact is the explicit oracle/acceptance read."""
    cfg, params = model
    eng = _engine(cfg, params, speculative_k=2)
    prompts = _prompts(2, lengths=(3, 4))
    wants = [_oneshot(cfg, params, p, 16) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.step()             # admission: prefill + lane-churn upload queued
    eng.step()             # flushes the churn upload (explicit device_put)
    assert eng.lanes.dirty is False and len(eng.lanes.requests) == 2
    with transfer_free():
        for _ in range(3):  # steady state: no admission, no retirement
            stats = eng.step()
            assert stats["decoded"] >= 2
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_armed_sentinels_with_speculation(model):
    """An engine built with the sentinel block enabled wraps the SPEC
    program in its own compile budget and runs the speculative step
    under the transfer guard — and still serves identical output."""
    from deepspeed_tpu.profiling.config import DeepSpeedSentinelConfig
    cfg, params = model
    sent_cfg = DeepSpeedSentinelConfig({"jax_sentinels": {
        "enabled": True, "compile_budget": 2, "transfer_guard": True}})
    eng = _engine(cfg, params, speculative_k=2, sentinel_config=sent_cfg)
    assert eng.decode_sentinel._fn is serving_engine_mod._spec_step_jit
    prompts = _prompts(3)
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


# -- corrupt_draft fault arm ------------------------------------------------

@pytest.mark.faults
def test_corrupt_draft_rejected_output_bitwise(model):
    """The adversarial-drafter arm: every draft token is scrambled to a
    guaranteed-different id on the armed steps. The verify forward must
    reject the garbage (zero acceptance on those steps) and the final
    output must stay bitwise identical to non-speculative greedy."""
    cfg, params = model
    fi = ServingFaultInjector({"corrupt_draft": {}})   # fire EVERY step
    eng = _engine(cfg, params, speculative_k=3, injector=fi)
    prompts = _prompts(2, lengths=(3, 5))
    wants = [_oneshot(cfg, params, p, 6) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=200)
    assert fi.fired["corrupt_draft"] >= 1
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    # with every draft scrambled, nothing can be accepted: the engine
    # degrades to exactly one token per lane per step
    assert eng.metrics.accept_rate() == 0.0
    assert eng.metrics.draft_proposed > 0


@pytest.mark.faults
def test_corrupt_draft_noop_without_speculation(model):
    """corrupt_draft with speculative_k=0 is inert (no drafts to
    scramble) and must not perturb the bitwise path."""
    cfg, params = model
    fi = ServingFaultInjector({"corrupt_draft": {}})
    eng = _engine(cfg, params, speculative_k=0, injector=fi)
    prompt = _prompts(1)[0]
    fut = eng.submit(prompt, max_new_tokens=4)
    eng.drain(max_steps=100)
    assert fut.result(timeout=1) == _oneshot(cfg, params, prompt, 4)
    assert fi.fired.get("corrupt_draft", 0) == 0


# -- int8 / bf16 KV parity --------------------------------------------------

def _token_match_rate(got, want):
    assert len(got) == len(want)
    return float(np.mean([g == w for g, w in zip(got, want)]))


@pytest.mark.parametrize("kv_dtype", ["int8", "bf16"])
@pytest.mark.parametrize("k", [0, 3])
def test_quantized_kv_parity_oracle(model, kv_dtype, k):
    """Quantized pools trade bitwise for threshold parity: greedy
    token-match rate against generate() stays above the pinned
    threshold, with and without speculation."""
    cfg, params = model
    eng = _engine(cfg, params, kv_cache_dtype=kv_dtype, speculative_k=k)
    assert eng.pool.k.dtype == (jnp.int8 if kv_dtype == "int8"
                                else jnp.bfloat16)
    prompts = _prompts(4)
    wants = [_oneshot(cfg, params, p, 6) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain(max_steps=400)
    rates = [_token_match_rate(f.result(timeout=1), w)
             for f, w in zip(futs, wants)]
    assert np.mean(rates) >= INT8_TOKEN_MATCH_THRESHOLD


def test_int8_pool_bytes_halved(model):
    """The HBM claim behind kv_cache_dtype: at equal MaxSlots the
    reported pool bytes drop to <=1/2 (bf16) and <=1/4 + scales (int8)
    of the fp32 pool, and Serving/kv_pool_bytes reports it."""
    cfg, params = model
    sizes = {}
    for kv_dtype in ("fp32", "bf16", "int8"):
        eng = _engine(cfg, params, kv_cache_dtype=kv_dtype)
        sizes[kv_dtype] = eng.pool.nbytes()
        assert eng.metrics.kv_pool_bytes == eng.pool.nbytes()
        assert eng.metrics.snapshot()["kv_pool_bytes"] == eng.pool.nbytes()
        assert eng.occupancy()["pool_bytes"] == eng.pool.nbytes()
        assert eng.occupancy()["kv_cache_dtype"] == kv_dtype
    assert sizes["bf16"] * 2 == sizes["fp32"]
    assert sizes["int8"] <= sizes["fp32"] // 2        # the halving claim
    assert sizes["int8"] < sizes["bf16"]              # scales stay small


def test_quantize_kv_roundtrip_and_attention_allclose(model):
    """quantize_kv/dequantize_kv: the roundtrip error is bounded by half
    an int8 grid cell per head, requantize with the same scale is a
    bitwise no-op (the fixed-scale append contract), and attention
    outputs computed over a roundtripped cache stay allclose."""
    cfg, params = model
    rng = np.random.RandomState(3)
    kv = jnp.asarray(rng.randn(2, 4, 8, 8).astype(np.float32))
    q, scale = quantize_kv(kv)
    assert q.dtype == jnp.int8 and scale.shape == (2, 4, 1, 1)
    back = dequantize_kv(q, scale)
    assert np.all(np.abs(np.asarray(back - kv))
                  <= np.asarray(scale) / 2 + 1e-7)
    # fixed-scale requantization is idempotent
    assert np.array_equal(np.asarray(requantize_kv(back, scale)),
                          np.asarray(q))
    # numpy twin agrees with the jax path bit-for-bit
    qn, sn = quantize_kv_np(np.asarray(kv))
    assert np.array_equal(qn, np.asarray(q))
    assert np.allclose(sn, np.asarray(scale))

    # attention outputs over exact vs roundtripped caches stay close
    n_heads = cfg.num_attention_heads
    shape = (cfg.num_hidden_layers, 1, n_heads, 16,
             cfg.hidden_size // n_heads)
    ck = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)
    cv = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)
    qk, sk = quantize_kv(ck)
    qv, sv = quantize_kv(cv)
    ids = jnp.asarray(rng.randint(0, 64, (1, 3)), jnp.int32)
    starts = jnp.asarray([8], jnp.int32)
    h_exact, _ = _forward_chunk(params, n_heads, (ck, cv), ids, starts)
    h_quant, _ = _forward_chunk(
        params, n_heads,
        (dequantize_kv(qk, sk), dequantize_kv(qv, sv)), ids, starts)
    assert np.allclose(np.asarray(h_exact), np.asarray(h_quant),
                       rtol=0.05, atol=0.05)


def test_int8_prefix_cache_entries_quantized(model):
    """In int8 pool mode prefix-cache entries are stored quantized
    (scales present, ~4x fewer bytes) and seed correctly on hits."""
    cfg, params = model
    eng = _engine(cfg, params, kv_cache_dtype="int8", prefix_cache_mb=4.0)
    prompts = _shared_prefix_prompts(4)
    wants = [_oneshot(cfg, params, p, 4) for p in prompts]
    rates = []
    for p, w in zip(prompts, wants):               # serial: later ones hit
        fut = eng.submit(p, max_new_tokens=4)
        eng.drain(max_steps=200)
        rates.append(_token_match_rate(fut.result(timeout=1), w))
    assert eng.prefix_stats()["hits"] >= 1
    assert np.mean(rates) >= INT8_TOKEN_MATCH_THRESHOLD
    entries = list(eng.prefix_cache._by_key.values())
    assert entries and all(e.k.dtype == np.int8 for e in entries)
    assert all(e.k_scale is not None for e in entries)


def test_int8_decode_recompile_pin(model):
    """The quantized decode program obeys the same churn pin as the
    plain one: admissions/retirements/slot reuse never recompile."""
    cfg, params = model
    eng = _engine(cfg, params, kv_cache_dtype="int8")
    sent = CompileSentinel(serving_engine_mod._decode_step_quant_jit, 1,
                           name="quantized decode step")
    prompts = _prompts(5)
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain(max_steps=400)
    for f in futs:
        assert len(f.result(timeout=1)) == 4
    assert sent.check() <= 1


# -- drafter unit -----------------------------------------------------------

def test_ngram_draft_bigram_lookup():
    """The drafter proposes the continuation of the LATEST earlier
    occurrence of the pending bigram, and falls back to repeating the
    pending token when no bigram matches."""
    # history: ... 1 2 [3 4] ... 1 2 <- pending bigram (1, 2) at pos 6
    h = jnp.asarray([9, 1, 2, 3, 4, 1, 2, 0, 0, 0], jnp.int32)
    drafts = np.asarray(_ngram_draft(h, jnp.asarray(6), 3))
    assert drafts.tolist() == [3, 4, 1]           # continuation after (1,2)
    # no match anywhere: repeat the pending token
    h2 = jnp.asarray([5, 6, 7, 8, 0, 0], jnp.int32)
    drafts2 = np.asarray(_ngram_draft(h2, jnp.asarray(3), 3))
    assert drafts2.tolist() == [8, 8, 8]
    # pos too small for any earlier bigram: fallback repeats h[pos]
    drafts3 = np.asarray(_ngram_draft(h, jnp.asarray(1), 2))
    assert drafts3.tolist() == [1, 1]


# -- config plumbing --------------------------------------------------------

def test_serving_config_spec_keys_validated():
    cfg = get_serving_config({"serving": {"speculative_k": 4,
                                          "kv_cache_dtype": "int8"}})
    assert cfg.speculative_k == 4 and cfg.kv_cache_dtype == "int8"
    assert get_serving_config({"serving": {}}).speculative_k == 0
    assert get_serving_config({"serving": {}}).kv_cache_dtype == "fp32"
    with pytest.raises(ValueError, match="speculative_k"):
        get_serving_config({"serving": {"speculative_k": -1}})
    with pytest.raises(ValueError, match="speculative_k"):
        get_serving_config({"serving": {"speculative_k": True}})
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        get_serving_config({"serving": {"kv_cache_dtype": "fp16"}})


def test_engine_rejects_bad_spec_config(model):
    cfg, params = model
    with pytest.raises(ValueError, match="speculative_k"):
        _engine(cfg, params, speculative_k=-2)
    with pytest.raises(ValueError, match="speculative_k"):
        _engine(cfg, params, speculative_k=64)    # >= max_seq_len
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _engine(cfg, params, kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        KVCachePool(2, 2, 4, 32, 8, kv_cache_dtype="fp16")
