"""Memory tiering: the prefix-cache spill tier + host-memory guard.

The load-bearing property is that the spill tier is INVISIBLE to
correctness: demotion, promotion, checksum rejection, torn disk writes,
and memory-pressure escalation may change WHAT gets recomputed, never
what gets returned — continuous-batched greedy output stays bitwise
equal to per-request ``generate()`` with the tier on, off, or actively
corrupted mid-episode. The integrity contract is drop-not-raise: a
corrupt or torn spill blob costs one re-prefill, never an error.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import generate
from deepspeed_tpu.inference.serving import (
    ServingConfig,
    ServingEngine,
    ServingFaultInjector,
)
from deepspeed_tpu.inference.serving.families import gpt2 as serving_engine_mod
from deepspeed_tpu.inference.serving.chaos import (
    MEMTIER_FAULT_KINDS,
    MemtierChaosHarness,
)
from deepspeed_tpu.inference.serving.handoff import HandoffFrameError
from deepspeed_tpu.inference.serving.prefix_cache import (
    MemoryPressureGuard,
    PrefixEntry,
    PrefixKVCache,
    SpillStore,
    decode_spill_blob,
    encode_spill_blob,
    read_host_rss_mb,
)
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from deepspeed_tpu.profiling import CompileSentinel

SHAPE = (2, 2, 5, 4)                    # [L, nh, P, hd]


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


def _oneshot(cfg, params, prompt, n_new):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(out)[0].tolist()


def _kv(dtype, seed=0):
    rng = np.random.RandomState(seed)
    k = rng.randn(*SHAPE)
    v = rng.randn(*SHAPE)
    if np.dtype(dtype) == np.int8:
        return (k * 10).astype(np.int8), (v * 10).astype(np.int8)
    return k.astype(dtype), v.astype(dtype)


def _spill_engine(cfg, params, **overrides):
    kw = dict(max_slots=2, max_queue=16, max_seq_len=32,
              prompt_buckets=(4, 8),
              prefix_cache_mb=0.005,        # one ~4 KiB entry, then evict
              prefix_spill_mb=4.0)
    kw.update(overrides)
    injector = kw.pop("injector", None)
    return ServingEngine(params, cfg, ServingConfig(**kw),
                         injector=injector)


def _serve_one(eng, prompt, want, n_new=5):
    fut = eng.submit(prompt, max_new_tokens=n_new)
    eng.drain(max_steps=200)
    assert fut.result(timeout=1) == want


# -- blob codec: bitwise round-trips per dtype ------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_spill_blob_roundtrip_bitwise(dtype):
    k, v = _kv(np.dtype(dtype))
    entry = PrefixEntry((3, 1, 4, 1, 5), k, v, impl="flash")
    out = decode_spill_blob(encode_spill_blob(entry))
    assert out.tokens == entry.tokens and out.impl == "flash"
    assert out.k.dtype == k.dtype and out.v.dtype == v.dtype
    assert out.k.tobytes() == k.tobytes()
    assert out.v.tobytes() == v.tobytes()
    assert out.k_scale is None and out.v_scale is None


def test_spill_blob_roundtrip_int8_with_scales():
    k, v = _kv(np.int8)
    rng = np.random.RandomState(1)
    k_scale = rng.rand(2, 2, 1, 1).astype(np.float32) + 0.01
    v_scale = rng.rand(2, 2, 1, 1).astype(np.float32) + 0.01
    entry = PrefixEntry((9, 8, 7, 6, 5), k, v,
                        k_scale=k_scale, v_scale=v_scale)
    out = decode_spill_blob(encode_spill_blob(entry))
    assert out.k.dtype == np.int8
    assert out.k.tobytes() == k.tobytes()
    assert out.v.tobytes() == v.tobytes()
    assert out.k_scale.tobytes() == k_scale.tobytes()
    assert out.v_scale.tobytes() == v_scale.tobytes()


def test_spill_blob_rejects_bit_flip_and_truncation():
    k, v = _kv(np.float32)
    blob = encode_spill_blob(PrefixEntry((1, 2, 3, 4, 5), k, v))
    flipped = bytearray(blob)
    flipped[len(flipped) // 2] ^= 0xFF
    with pytest.raises(HandoffFrameError):
        decode_spill_blob(bytes(flipped))
    with pytest.raises(HandoffFrameError):
        decode_spill_blob(blob[:len(blob) // 2])


# -- SpillStore: LRU tiers, verify-or-drop, fault surface -------------------

def _entry(tokens, seed=0, impl="dense"):
    k, v = _kv(np.float32, seed=seed)
    return PrefixEntry(tuple(tokens), k, v, impl=impl)


def test_spillstore_corrupt_entry_dropped_not_raised():
    st = SpillStore(1 << 20)
    assert st.put(_entry((1, 2, 3)))
    assert st.corrupt_one() == ("dense", 1, 2, 3)
    n, key = st.match((1, 2, 3, 9), impl="dense")
    assert n == 3
    assert st.take(key) is None         # dropped, never raised
    assert st.corrupt_dropped == 1
    assert len(st) == 0                 # the record did not survive
    # the store still works after the drop
    assert st.put(_entry((1, 2, 3)))
    assert st.take(("dense", 1, 2, 3)) is not None


def test_spillstore_ram_overflow_demotes_to_disk_and_promotes(tmp_path):
    e = _entry((1, 2, 3, 4, 5))
    blob_len = len(encode_spill_blob(e))
    st = SpillStore(blob_len + 16, spill_dir=str(tmp_path))
    assert st.put(e)
    assert st.put(_entry((6, 7, 8), seed=1))    # LRU -> disk tier
    stats = st.stats()
    assert stats["ram_entries"] == 1 and stats["disk_entries"] == 1
    assert st.disk_demotions == 1
    out = st.take(("dense", 1, 2, 3, 4, 5))     # promoted FROM DISK
    assert out is not None
    assert out.k.tobytes() == e.k.tobytes()
    assert st.stats()["disk_entries"] == 0      # file consumed + removed


def test_spillstore_torn_disk_write_invisible_on_reload(tmp_path):
    """A disk write injected torn (truncated, under its final name —
    the crash the atomic tmp/fsync/rename protocol normally rules out)
    must be caught by the framing at promotion time and dropped."""
    shots = [1]
    st = SpillStore(1, spill_dir=str(tmp_path))     # RAM never fits
    st.torn_write_hook = lambda: bool(shots and shots.pop())
    assert st.put(_entry((1, 2, 3)))                # lands torn on disk
    assert st.take(("dense", 1, 2, 3)) is None
    assert st.corrupt_dropped == 1
    # hook exhausted: the next write is atomic and round-trips
    e2 = _entry((4, 5, 6), seed=2)
    assert st.put(e2)
    out = st.take(("dense", 4, 5, 6))
    assert out is not None and out.k.tobytes() == e2.k.tobytes()


def test_spillstore_shed_clears_both_tiers(tmp_path):
    e = _entry((1, 2, 3, 4, 5))
    st = SpillStore(len(encode_spill_blob(e)) + 16, spill_dir=str(tmp_path))
    st.put(e)
    st.put(_entry((6, 7, 8), seed=1))
    assert st.shed() == 2
    assert len(st) == 0 and st.ram_bytes == 0 and st.disk_bytes == 0
    assert list(tmp_path.iterdir()) == []       # disk tier emptied too


# -- PrefixKVCache demotion/promotion ---------------------------------------

def test_cache_eviction_demotes_and_lookup_promotes():
    a, b = _entry((1, 2, 3, 4, 5)), _entry((6, 7, 8, 9, 10), seed=1)
    cache = PrefixKVCache(a.nbytes + 32, spill_budget_bytes=1 << 20)
    cache.insert(a.tokens, a.k, a.v)
    cache.insert(b.tokens, b.k, b.v)            # evicts a -> spill
    assert cache.evictions == 1 and len(cache.spill) == 1
    n, entry = cache.acquire((1, 2, 3, 4, 5, 99))
    assert n == 5 and entry is not None
    assert entry.k.tobytes() == a.k.tobytes()   # bitwise through the tier
    assert cache.spill_promotions == 1 and cache.spill_hits == 1
    assert len(cache.spill) == 1                # b was demoted to make room
    cache.release(entry)


def test_cache_promotion_counts_one_hit_per_promotion():
    a, b = _entry((1, 2, 3, 4, 5)), _entry((6, 7, 8, 9, 10), seed=1)
    cache = PrefixKVCache(a.nbytes + 32, spill_budget_bytes=1 << 20)
    cache.insert(a.tokens, a.k, a.v)
    cache.insert(b.tokens, b.k, b.v)
    n, entry = cache.acquire((1, 2, 3, 4, 5))   # promotion: 1 spill hit
    cache.release(entry)
    n, entry = cache.acquire((1, 2, 3, 4, 5))   # live hit: a spill MISS
    cache.release(entry)
    assert cache.spill_hits == 1 and cache.spill_misses == 1


def test_cache_corrupt_spill_falls_through_to_live_result():
    a, b = _entry((1, 2, 3, 4, 5)), _entry((6, 7, 8, 9, 10), seed=1)
    events = []
    cache = PrefixKVCache(a.nbytes + 32, spill_budget_bytes=1 << 20,
                          listener=events.append)
    cache.insert(a.tokens, a.k, a.v)
    cache.insert(b.tokens, b.k, b.v)            # a spilled
    assert cache.corrupt_spilled() is not None
    n, entry = cache.acquire((1, 2, 3, 4, 5))   # promotion fails its crc
    assert n == 0 and entry is None             # clean miss, no raise
    assert cache.spill.corrupt_dropped == 1
    assert "spill_corrupt" in events


# -- MemoryPressureGuard ----------------------------------------------------

def test_guard_climbs_and_recovers_with_hysteresis():
    class Ladder:
        rung = 0

        def set_rung(self, rung, reason="forced"):
            self.rung = rung

    cache = PrefixKVCache(1 << 20, spill_budget_bytes=1 << 20)
    cache.insert((1, 2, 3), *_kv(np.float32)[:2])
    cache._evict_locked(cache._by_key[("dense", 1, 2, 3)])  # seed the spill
    assert len(cache.spill) == 1
    rss = [200.0]
    levels = []
    ladder = Ladder()
    g = MemoryPressureGuard(100.0, cache=cache, ladder=ladder,
                            read_rss_mb=lambda: rss[0],
                            listener=lambda lv, r: levels.append(lv))
    for _ in range(2):
        g.check()
    assert g.level == 1 and len(cache.spill) == 0   # shed_spill fired
    assert not g.inserts_paused
    for _ in range(2):
        g.check()
    assert g.level == 2 and g.inserts_paused
    for _ in range(2):
        g.check()
    assert g.level == 3 and ladder.rung == 1        # climbed the ladder
    rss[0] = 95.0                                   # hysteresis band: hold
    for _ in range(4):
        g.check()
    assert g.level == 3
    rss[0] = 50.0                                   # below recover line
    for _ in range(8):
        g.check()
    assert g.level == 0 and not g.inserts_paused
    assert levels == [1, 2, 3, 2, 1, 0]             # edge-triggered only
    assert g.escalations == 3 and g.recoveries == 3


def test_guard_inert_without_rss_signal():
    g = MemoryPressureGuard(100.0, read_rss_mb=lambda: None)
    for _ in range(5):
        assert g.check() == 0
    assert read_host_rss_mb() is None or read_host_rss_mb() > 0


# -- the engine: bitwise oracle with the tier on ----------------------------

def _spilled_wave(cfg, params, eng, rng):
    """Serve A, then B (evicting A to spill), and return (A, want_A) so
    the caller can hit the spilled entry."""
    A = rng.randint(0, 64, (8,)).tolist()
    B = rng.randint(0, 64, (8,)).tolist()
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    _serve_one(eng, B, _oneshot(cfg, params, B, 5))
    assert len(eng.prefix_cache.spill) >= 1
    return A


def test_oracle_spilled_hit_promotes_bitwise(model):
    """Schedule 1 (sequential waves): an entry demoted to the spill tier
    and promoted back must seed a bitwise-identical decode, and the
    promotion must count exactly one spill hit."""
    cfg, params = model
    eng = _spill_engine(cfg, params)
    A = _spilled_wave(cfg, params, eng, np.random.RandomState(3))
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    st = eng.prefix_cache.stats()
    assert st["spill_promotions"] == 1 and st["spill_hits"] == 1
    assert eng.metrics.prefill_reused_tokens > 0
    assert eng.metrics.spill_hit_rate() > 0


def test_oracle_mid_decode_admission_with_spill(model):
    """Schedule 2: requests join while others are mid-decode, with the
    spill tier armed and a shared prefix bouncing through it."""
    cfg, params = model
    eng = _spill_engine(cfg, params)
    rng = np.random.RandomState(5)
    A = _spilled_wave(cfg, params, eng, rng)
    prompts = [A[:6] + rng.randint(0, 64, (2,)).tolist() for _ in range(3)]
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]
    futs = [eng.submit(prompts[0], max_new_tokens=5)]
    eng.step()
    eng.step()
    futs += [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
    eng.drain(max_steps=200)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want


def test_oracle_trickle_with_corruption_mid_episode(model):
    """Schedule 3 (trickle) with the corrupt_spill_entry arm firing mid
    episode: every request still completes bitwise — the corrupt blob
    costs a re-prefill, not an error — and the drop is counted."""
    cfg, params = model
    injector = ServingFaultInjector()
    eng = _spill_engine(cfg, params, injector=injector)
    rng = np.random.RandomState(7)
    A = _spilled_wave(cfg, params, eng, rng)
    injector.arm_serving("corrupt_spill_entry", times=1)
    eng.step()                                  # the arm fires
    assert injector.fired.get("corrupt_spill_entry") == 1
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))     # promotion fails
    st = eng.prefix_cache.stats()
    assert st["spill"]["corrupt_dropped"] == 1
    assert st["spill_promotions"] == 0
    assert eng.metrics.spill_corrupt_total == 1
    # the NEXT wave re-populates and the tier serves again
    B = rng.randint(0, 64, (8,)).tolist()
    _serve_one(eng, B, _oneshot(cfg, params, B, 5))
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    assert eng.prefix_cache.stats()["spill_promotions"] == 1


def test_oracle_identical_with_spill_on_and_off(model):
    """Same traffic, spill on vs off: outputs agree token-for-token
    (the tier only changes what is recomputed)."""
    cfg, params = model
    rng = np.random.RandomState(11)
    A = rng.randint(0, 64, (8,)).tolist()
    B = rng.randint(0, 64, (8,)).tolist()
    outs = []
    for spill_mb in (0.0, 4.0):
        eng = _spill_engine(cfg, params, prefix_spill_mb=spill_mb)
        got = []
        for p in (A, B, A, B):
            fut = eng.submit(p, max_new_tokens=5)
            eng.drain(max_steps=200)
            got.append(fut.result(timeout=1))
        outs.append(got)
    assert outs[0] == outs[1]


def test_torn_spill_write_arm_invisible_end_to_end(model, tmp_path):
    """Disk-tier spill with the torn_spill_write arm: the truncated file
    is rejected at promotion, the request falls through to a full
    prefill, and output stays bitwise."""
    cfg, params = model
    injector = ServingFaultInjector()
    eng = _spill_engine(cfg, params, injector=injector,
                        prefix_spill_mb=0.001,  # RAM tier never fits
                        prefix_spill_dir=str(tmp_path))
    injector.arm_serving("torn_spill_write", times=1)
    A = _spilled_wave(cfg, params, eng, np.random.RandomState(13))
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    st = eng.prefix_cache.stats()
    assert st["spill"]["corrupt_dropped"] >= 1
    assert injector.fired.get("torn_spill_write") == 1


def test_host_mem_pressure_arm_climbs_engine_ladder(model):
    """The host_mem_pressure arm: the guard reads fake over-watermark
    RSS, walks shed-spill -> pause-inserts -> degrade, the engine ladder
    climbs, and with the arm exhausted everything recovers — with live
    bitwise traffic throughout."""
    cfg, params = model
    injector = ServingFaultInjector()
    eng = _spill_engine(cfg, params, injector=injector,
                        host_mem_watermark_mb=1 << 20)  # real RSS never trips
    A = _spilled_wave(cfg, params, eng, np.random.RandomState(17))
    assert len(eng.prefix_cache.spill) >= 1
    injector.arm_serving("host_mem_pressure", times=6)
    for _ in range(6):
        eng.step()
    guard = eng._mem_guard
    assert guard.level == 3 and guard.inserts_paused
    assert len(eng.prefix_cache.spill) == 0         # level 1 shed it
    assert eng._degrade_rung >= 1                   # level 3 climbed
    assert eng.metrics.snapshot()["host_rss_mb"] > 0
    # arm exhausted: real RSS is far below the watermark, so the guard
    # walks back down; traffic stays bitwise the whole way
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    for _ in range(3 * guard.recover_checks):
        eng.step()
    assert guard.level == 0 and not guard.inserts_paused
    B = np.random.RandomState(19).randint(0, 64, (7,)).tolist()
    _serve_one(eng, B, _oneshot(cfg, params, B, 5))


def test_inserts_paused_under_guard(model):
    cfg, params = model
    injector = ServingFaultInjector()
    eng = _spill_engine(cfg, params, injector=injector,
                        host_mem_watermark_mb=1 << 20)
    # enough shots that the guard stays pressured through the serve
    injector.arm_serving("host_mem_pressure", times=50)
    for _ in range(4):
        eng.step()
    assert eng._mem_guard.inserts_paused
    rng = np.random.RandomState(23)
    A = rng.randint(0, 64, (8,)).tolist()
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    assert eng._mem_guard.inserts_paused            # still pressured
    assert len(eng.prefix_cache) == 0               # insert was skipped


def test_promotion_never_recompiles_decode(model):
    """CompileSentinel pin: serving a spilled-hit promotion compiles the
    decode step zero additional times — the promoted entry seeds the
    lane through the SAME one-transfer prefill path as a live hit."""
    cfg, params = model
    eng = _spill_engine(cfg, params)
    A = _spilled_wave(cfg, params, eng, np.random.RandomState(29))
    sent = CompileSentinel(serving_engine_mod._decode_step_jit, 0,
                           name="decode step during promotion")
    _serve_one(eng, A, _oneshot(cfg, params, A, 5))
    assert eng.prefix_cache.stats()["spill_promotions"] == 1
    assert sent.check() == 0


# -- admission relief under pool pressure -----------------------------------

def test_pool_exhaustion_triggers_relief_then_requeue(model):
    """The OOM-safe admission satellite: a full pool sheds unreferenced
    host-side ballast (live entries demote, spill drops) before the
    request requeues — and the request completes once pages free."""
    cfg, params = model
    # 3 slots over a 4-page shared pool: two ~2-page admissions exhaust
    # the pages while a slot is still free — the can_allocate relief
    # path, not slot backpressure
    eng = _spill_engine(cfg, params, max_slots=3,
                        kv_page_tokens=8, kv_pool_tokens=32)
    rng = np.random.RandomState(31)
    A = _spilled_wave(cfg, params, eng, rng)
    assert len(eng.prefix_cache) >= 1 and len(eng.prefix_cache.spill) >= 1
    prompts = [rng.randint(0, 64, (6,)).tolist() for _ in range(3)]
    wants = [_oneshot(cfg, params, p, 5) for p in prompts]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.step()                      # first claims the pool; rest hit the wall
    eng.drain(max_steps=300)
    for f, want in zip(futs, wants):
        assert f.result(timeout=1) == want
    assert eng._pool_relief_attempts >= 1
    assert eng.scheduler.requeues >= 1


# -- config validation ------------------------------------------------------

def test_spill_config_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="prefix_spill_mb"):
        ServingEngine(params, cfg, ServingConfig(prefix_spill_mb=-1.0))
    with pytest.raises(ValueError, match="live prefix cache"):
        ServingEngine(params, cfg, ServingConfig(
            prefix_cache_mb=0.0, prefix_spill_mb=1.0))
    with pytest.raises(ValueError, match="prefix_spill_dir"):
        ServingEngine(params, cfg, ServingConfig(
            prefix_cache_mb=1.0, prefix_spill_mb=0.0,
            prefix_spill_dir="/tmp/x"))
    with pytest.raises(ValueError, match="host_mem_watermark_mb"):
        ServingEngine(params, cfg, ServingConfig(
            host_mem_watermark_mb=-5.0))


def test_memtier_chaos_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kinds"):
        MemtierChaosHarness(None, None, lambda p, n: [], [],
                            faults=MEMTIER_FAULT_KINDS + ("nope",))
