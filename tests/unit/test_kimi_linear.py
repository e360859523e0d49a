"""Kimi-Linear at a small size on the CPU: the program (``models/
kimi_linear.py``, ``parallel/expert.py``, ``ServingEngine`` through its
family seam) against the plain reference (``benchmarks/refs/
kimi_linear_ref.py``), which follows the published equations token by token.

Pattern KDA+dense, KDA, KDA, MLA, KDA; hidden 64, 2 heads of 16, 8 experts
top-2 of which 4 are held; float32 parameters, so the program and the
reference may differ by rounding order only."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import kimi_linear_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families.kimi_linear import (
    KimiLinearFamily,
)
from deepspeed_tpu.inference.serving.family import (
    UnsupportedOptionError,
    family_for,
)
from deepspeed_tpu.inference.serving.kv_pool import (
    HybridStatePool,
    PageStateError,
    PoolExhaustedError,
)
from deepspeed_tpu.models import kimi_linear as kl
from deepspeed_tpu.parallel import expert as expert_mod

CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 2, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"full_attn_layers": [4, 8],
                           "kda_layers": [1, 2, 3, 5, 6, 7], "head_dim": 16,
                           "num_heads": 2, "short_conv_kernel_size": 4},
    "share": {"num_experts_published": 8, "experts_first": 0},
}
CHUNK = 64

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile"))


def model_config(cfg):
    share = cfg["share"]
    return kl.KimiLinearConfig.from_dict(
        dict(cfg, num_experts=share["num_experts_published"]),
        experts_held=(share["experts_first"], cfg["num_experts"]))


@functools.lru_cache(maxsize=None)
def _weights(held, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, num_experts=held)), seed, jnp.float32)


def make(cfg=CFG, seed=7, slow_decay=False):
    flat = _weights(cfg["num_experts"], seed)
    if slow_decay:
        # A_log = -4: g is about -0.013 a token, so the state keeps half of
        # itself for some 55 tokens and a state carried wrongly shows
        flat = {k: (jnp.full_like(v, -4.0) if k.endswith("A_log") else v)
                for k, v in flat.items()}
    return flat, weights_mod.nest(flat), model_config(cfg)


PAD_T = 192


@jax.jit
def _reference_pass(flat, ids):
    return ref.logits_at(flat, ids, jnp.arange(ids.shape[1])[None],
                         dims=ref.dims_of(CFG))


def reference_logits(flat, ids):
    """[T, V] logits of one full forward pass over ``ids [T]`` (padded to
    one length so that the reference compiles once: it is causal, so what
    follows a position cannot reach it)."""
    row = np.zeros((1, PAD_T), np.int32)
    row[0, :len(ids)] = ids
    return np.asarray(_reference_pass(flat, jnp.asarray(row)))[0, :len(ids)]


def engine(params, mcfg, **over):
    kw = dict(max_slots=3, max_queue=32, max_seq_len=256,
              prompt_buckets=(200,), kv_cache_dtype="fp32",
              kv_page_tokens=16, prefill_chunk_tokens=CHUNK)
    kw.update(over)
    return ServingEngine(params, mcfg, ServingConfig(**kw))


# -- (a) prefill then decode through ServingEngine --------------------------

@pytest.mark.parametrize("slow_decay", [False, True])
def test_engine_logits_match_the_reference_forward_pass(slow_decay):
    """Prompts shorter than, equal to and longer than a chunk, several lanes
    at once, and more requests than lanes, so that lanes get a second
    occupant whose state must start from zero. Every decode step's logits
    are compared, lane by lane, with the reference's one forward pass over
    the prompt and the tokens served so far."""
    flat, params, mcfg = make(slow_decay=slow_decay)
    eng = engine(params, mcfg)
    assert isinstance(eng.family, KimiLinearFamily)
    rng = np.random.default_rng(1)
    lengths = (10, CHUNK, 150, 33, CHUNK + 1, 2 * CHUNK, 5)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in lengths]
    futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    seen = {}                 # request -> its steps' logits, in order
    occupants = {}
    eng.family.keep_logits = True
    real = eng.family.decode_step

    def spy(guard):
        lanes = {s: r.id for s, r in eng.lanes.requests.items()}
        out = real(guard)
        logits = np.asarray(eng.family.last_logits)
        for slot, rid in lanes.items():
            occupants.setdefault(slot, set()).add(rid)
            seen.setdefault(rid, []).append(logits[slot])
        return out

    eng.family.decode_step = spy
    assert eng.drain(max_steps=500) < 500
    assert max(len(v) for v in occupants.values()) >= 2   # a lane was reused
    worst = 0.0
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        assert len(toks) == 9
        want = reference_logits(flat, np.concatenate([p, toks]))
        # the first token comes from the prefill program
        assert toks[0] == int(want[len(p) - 1].argmax())
        # a request's j-th step reads its token j; one step more than it
        # needs may have been dispatched before its last token was read
        assert 8 <= len(seen[f.request_id]) <= 9
        assert toks[1:] == [int(want[len(p) + j].argmax()) for j in range(8)]
        for j, got in enumerate(seen[f.request_id][:8]):
            worst = max(worst, float(np.abs(
                got - want[len(p) + j]).max()))
    assert worst < 2e-4, worst
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == sum(lengths)
    assert snap["prefill_chunks"] >= 6
    assert eng.pool.slot_resets == len(prompts)
    # every call dispatches a step and reads the one before: all but the
    # first call read one
    assert snap["moe_layer_steps"] == 4 * (snap["decode_steps"] - 1)
    assert snap["tokens_emitted"] == 8 * len(prompts)
    assert 0 < snap["moe_experts_touched"] <= snap["moe_picks_here"]
    assert snap["state_pool_bytes"] == eng.pool.slot_bytes() > 0
    assert snap["latent_pool_bytes"] == eng.pool.paged_bytes() > 0


def test_a_lanes_second_occupant_starts_from_a_zeroed_state():
    """One lane, two requests in turn: the second's tokens equal what it
    gets alone in a fresh engine (slow decay, so a state left behind by the
    first occupant would show)."""
    _, params, mcfg = make(slow_decay=True)
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 96, n).astype(np.int32) for n in (70, 40))
    eng = engine(params, mcfg)
    fa = eng.submit(a, max_new_tokens=6)
    eng.drain(max_steps=200)
    fb = eng.submit(b, max_new_tokens=6)      # lowest free slot: a's again
    eng.step()
    assert eng.family._prefilling[0].slot == 0 and eng.pool.allocations == 2
    eng.drain(max_steps=200)
    alone = engine(params, mcfg)
    fc = alone.submit(b, max_new_tokens=6)
    alone.drain(max_steps=200)
    assert fb.result(timeout=1) == fc.result(timeout=1)
    assert fa.result(timeout=1) != fb.result(timeout=1)
    assert eng.pool.slot_resets == 2


def test_prompt_padding_is_less_than_one_chunk_a_prompt():
    """One program for every length: a prompt runs ceil(len / chunk) chunks
    and no bucket: 5, 64 and 67 tokens take 1 + 1 + 2 calls."""
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for n in (5, CHUNK, CHUNK + 3):
        eng.submit(np.arange(n) % 96, max_new_tokens=2)
    eng.drain(max_steps=100)
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == 5 + CHUNK + CHUNK + 3
    assert snap["prefill_chunks"] == 4 and snap["prefill_calls"] == 3
    assert snap["prefill_positions_run"] == 4 * CHUNK


def test_background_loop_streams_tokens():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    got = []
    eng.start()
    try:
        fut = eng.submit(np.arange(20) % 96, max_new_tokens=5,
                         stream_cb=lambda rid, tok: got.append(tok))
        assert fut.result(timeout=120) == got and len(got) == 5
    finally:
        eng.stop()


# -- (b) chunkwise KDA against the recurrence -------------------------------

@pytest.mark.parametrize("decay", ["fast", "slow"])
def test_chunkwise_kda_matches_the_recurrence(decay):
    rng = np.random.default_rng(0)
    T, H, D = 3 * kl.KDA_CHUNK, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
               for _ in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rate = {"fast": 0.69, "slow": 0.013}[decay]
    g = -rate * jnp.asarray(rng.uniform(0.5, 1.5, (T, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (T, H)), jnp.float32)
    S0 = jnp.asarray(rng.normal(size=(H, D, D)), jnp.float32)

    def step(S, xs):
        return kl.kda_recurrent_step(S, *xs)

    S_want, o_want = jax.lax.scan(step, S0, (q, k, v, g, beta))
    S_got, o_got = kl.kda_chunkwise(S0, q, k, v, g, beta)
    np.testing.assert_allclose(o_got, o_want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S_got, S_want, atol=2e-4, rtol=2e-4)
    if decay == "slow":
        # the initial state still weighs in the last chunk's outputs: a
        # state dropped at a chunk boundary would show
        _, o_zero = kl.kda_chunkwise(jnp.zeros_like(S0), q, k, v, g, beta)
        at = 2 * kl.KDA_CHUNK
        assert float(jnp.abs(o_zero[at] - o_want[at]).max()) > 1e-2


def test_chunkwise_kda_padding_leaves_the_state_alone():
    rng = np.random.default_rng(1)
    T, H, D = kl.KDA_CHUNK, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
               for _ in range(3))
    live = (jnp.arange(T) < 10)
    g = jnp.where(live[:, None, None], -0.3, 0.0) * jnp.ones((T, H, D))
    beta = jnp.where(live[:, None], 0.5, 0.0) * jnp.ones((T, H))
    S0 = jnp.asarray(rng.normal(size=(H, D, D)), jnp.float32)
    S_all, _ = kl.kda_chunkwise(S0, q, k, v, g, beta)
    S_ten = S0
    for t in range(10):
        S_ten, _ = kl.kda_recurrent_step(S_ten, q[t], k[t], v[t], g[t],
                                         beta[t])
    np.testing.assert_allclose(S_all, S_ten, atol=1e-4, rtol=1e-4)


# -- (c) absorbed MLA decode against the expanded form ----------------------

def test_absorbed_mla_decode_matches_expanded_prefill():
    """The same tokens through the expanded chunk form and, one token at a
    time, through the absorbed decode form: the same outputs and the same
    latent rows in the pages."""
    _, params, mcfg = make()
    p = params["layers"]["4"]["self_attn"]
    rng = np.random.default_rng(2)
    T, pt = 40, 16
    x = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(4)[None], jnp.int32)
    pool = jnp.zeros((1, 6, mcfg.latent_width, pt), jnp.float32)
    y_pre, pool_pre = kl.mla_prefill(
        p, mcfg, x, pool, 0, tables, jnp.zeros(1, jnp.int32),
        jnp.asarray([T], jnp.int32), pt)
    pool_dec = pool
    decode = jax.jit(lambda x_t, pool, t: kl.mla_decode(
        p, mcfg, x_t, pool, 0, tables, t, jnp.asarray([True]), pt))
    for t in range(T):
        y_t, pool_dec = decode(x[:, t], pool_dec, jnp.asarray([t], jnp.int32))
        np.testing.assert_allclose(y_t[0], y_pre[0, t], atol=2e-5, rtol=2e-4)
    def rows(pool):
        return np.asarray(jnp.swapaxes(pool[0, 1:], 1, 2)).reshape(
            -1, mcfg.latent_width)[:T]

    live = rows(pool_pre)
    np.testing.assert_allclose(rows(pool_dec), live,
        atol=1e-6)
    # and against the reference's expanded mixer
    ref.bind(CFG)
    flat_p = weights_mod.flatten(p)
    want = jax.jit(lambda w, x: ref.mla_mixer(w, x, ref.dims_of(CFG), "f32"))(
        flat_p, x[0, :T])
    np.testing.assert_allclose(y_pre[0, :T], want, atol=2e-5, rtol=2e-4)


# -- (d) the shares add up to the uncut layer -------------------------------

def test_the_two_shares_add_up_to_the_uncut_expert_layer():
    """What the shares (0-3) and (4-7) give, with the shared expert counted
    once, adds up to the uncut reference's expert layer (all 8 experts)."""
    whole = dict(CFG, num_experts=8)
    flat, params, _ = make(cfg=whole)
    m = weights_mod.flatten(params["layers"]["2"]["mlp"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    want = jax.jit(lambda m, x: ref.expert_ffn(m, x, ref.dims_of(whole),
                                               "f32"))(m, x)
    shared_only = ref._swiglu(x, m["shared_experts/gate_proj/kernel"],
                              m["shared_experts/up_proj/kernel"],
                              m["shared_experts/down_proj/kernel"], "f32")
    mlp = params["layers"]["2"]["mlp"]
    total = jnp.zeros_like(x)
    picks = 0
    for first in (0, 4):
        part = dict(mlp, experts={k: v[first:first + 4]
                                  for k, v in mlp["experts"].items()})
        y, stats = moe_ffn(
            part, x, k=2, scaling=2.446, renormalize=True, held=(first, 4),
            tile=8)
        total = total + y
        picks += int(stats[0])
        # the reference, given the same share through the same keys
        half = dict(CFG, share={"num_experts_published": 8,
                                "experts_first": first})
        want_part = jax.jit(lambda m, x, half=half: ref.expert_ffn(
            m, x, ref.dims_of(half), "f32"))(weights_mod.flatten(part), x)
        np.testing.assert_allclose(y, want_part, atol=2e-6, rtol=2e-5)
    assert picks == 50 * 2                    # every pick fell on one share
    np.testing.assert_allclose(total - shared_only, want, atol=5e-6,
                               rtol=5e-5)


# -- (e) no token is dropped ------------------------------------------------

@pytest.mark.parametrize("tile", [8, 128])
def test_no_token_is_dropped_when_all_pick_the_same_experts(tile):
    """A bias that makes every token pick experts 1 and 2: both are held,
    their load is every token, and every token's output is the weighted sum
    of exactly those two experts."""
    _, params, _ = make()
    mlp = dict(params["layers"]["3"]["mlp"])
    bias = jnp.zeros(8).at[jnp.asarray([1, 2])].set(10.0)
    mlp["gate"] = dict(mlp["gate"], e_score_correction_bias=bias)
    del mlp["shared_experts"]
    T = 300
    x = jnp.asarray(np.random.default_rng(5).normal(size=(T, 64)), jnp.float32)
    y, stats = moe_ffn(
        mlp, x, k=2, scaling=2.446, renormalize=True, held=(0, 4), tile=tile)
    assert [int(s) for s in stats] == [2 * T, 2, T]
    idx, w = expert_mod.sigmoid_topk_routing(
        x, mlp["gate"]["kernel"], bias, 2, 2.446)
    assert set(np.asarray(idx).ravel().tolist()) == {1, 2}
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.446, rtol=1e-5)
    ex = mlp["experts"]
    want = 0.0
    for j in range(2):
        e = np.asarray(idx[:, j])
        a = jnp.einsum("td,tdf->tf", x, ex["gate_proj"][e])
        b = jnp.einsum("td,tdf->tf", x, ex["up_proj"][e])
        want = want + w[:, j:j + 1] * jnp.einsum(
            "tf,tfd->td", jax.nn.silu(a) * b, ex["down_proj"][e])
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0      # no all-zero row


def test_tokens_that_are_not_live_touch_no_expert():
    _, params, _ = make()
    mlp = params["layers"]["2"]["mlp"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(12, 64)),
                    jnp.float32)
    live = jnp.arange(12) < 3
    _, stats = moe_ffn(
        mlp, x, live, k=2, scaling=2.446, renormalize=True, held=(0, 4),
        tile=8)
    _, only = moe_ffn(
        mlp, x[:3], k=2, scaling=2.446, renormalize=True, held=(0, 4), tile=8)
    assert [int(s) for s in stats] == [int(s) for s in only]


# -- (f) each unsupported option raises, by name ----------------------------

UNSUPPORTED = {
    "prefix_cache_mb": dict(prefix_cache_mb=4.0),
    "prefix_spill_mb": dict(prefix_cache_mb=4.0, prefix_spill_mb=1.0),
    "speculative_k": dict(speculative_k=2),
    "kv_cache_dtype='int8'": dict(kv_cache_dtype="int8"),
    "kv_cache_dtype='bf16'": dict(kv_cache_dtype="bf16"),
    "attention_impl='flash'": dict(attention_impl="flash"),
    "attention_impl='pallas_decode'": dict(attention_impl="pallas_decode"),
    "attention_kernel": dict(attention_kernel="xla"),
    "mesh_shape": dict(mesh_shape=(1, 2)),
    "prefill_chunk_tokens=0": dict(prefill_chunk_tokens=0),
    "prefill_chunk_tokens=100": dict(prefill_chunk_tokens=100),
    "fault_injection": dict(fault_injection={"slow_decode": {}}),
}


@pytest.mark.parametrize("option", sorted(UNSUPPORTED))
def test_unsupported_option_raises_at_construction_by_name(option):
    _, params, mcfg = make()
    with pytest.raises(UnsupportedOptionError) as err:
        engine(params, mcfg, **UNSUPPORTED[option])
    named = option.split("=")[0]
    if named == "prefix_spill_mb":
        named = "prefix_cache_mb"         # the cache it needs is refused first
    assert f"serving.{named}" in str(err.value)
    assert "kimi_linear" in str(err.value)


def test_fp32_latent_rows_are_refused_for_bfloat16_parameters():
    _, params, mcfg = make()
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    with pytest.raises(UnsupportedOptionError, match="kv_cache_dtype='fp32'"):
        engine(half, mcfg, kv_cache_dtype="fp32")
    engine(half, mcfg, kv_cache_dtype="bf16")


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4),
                 lambda: eng.handoff_install(0, {}, []),
                 lambda: eng.resume_handoff(0, [1], 2, 3)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


def test_gpt2_family_is_the_default_and_refuses_no_option():
    from deepspeed_tpu.models.gpt2 import GPT2Config

    fam = family_for(GPT2Config(vocab_size=64, hidden_size=16,
                                num_hidden_layers=1, num_attention_heads=2,
                                max_position_embeddings=32))
    assert fam.name == "gpt2"
    fam.check_options(ServingConfig(), None)
    fam.check_options(ServingConfig(prefix_cache_mb=8, speculative_k=2,
                                    kv_cache_dtype="int8",
                                    prefill_chunk_tokens=48), None)
    fam.refuse_handoff()


# -- the state pool and the configuration -----------------------------------

def test_hybrid_state_pool_needs_a_slot_and_pages_and_zeroes_on_reuse():
    pool = HybridStatePool(
        2, 64, paged={"latent": (1, 8, jnp.float32)},
        slotted={"kda": (2, (2, 4, 4), jnp.float32)}, page_tokens=16,
        pool_tokens=96)
    assert pool.state["latent"].shape == (1, 7, 8, 16)      # 6 pages + null
    assert pool.state["kda"].shape == (2, 2, 2, 4, 4)       # a row a slot
    a = pool.allocate(64)                                   # 4 of 6 pages
    assert not pool.can_allocate(48)                        # a slot, no pages
    with pytest.raises(PoolExhaustedError):
        pool.allocate(48)
    b = pool.allocate(20)
    assert not pool.can_allocate(1)                         # pages, no slot
    pool.state["kda"] = pool.state["kda"].at[:, a].set(1.0)
    pool.free(a)
    with pytest.raises(PageStateError):
        pool.reset_slot(a)
    a2 = pool.allocate(16)
    assert a2 == a and float(pool.state["kda"][:, a2].sum()) > 0
    pool.reset_slot(a2)
    assert float(jnp.abs(pool.state["kda"][:, a2]).sum()) == 0.0
    occ = pool.occupancy()
    assert occ["in_use"] == 2 and occ["slot_resets"] == 1
    assert occ["pool_bytes"] == pool.paged_bytes() + pool.slot_bytes()
    pool.free(b)


def test_config_reads_the_layer_kinds_from_the_published_lists():
    mcfg = model_config(CFG)
    assert [mcfg.layer_kind(i) for i in range(1, 6)] == [
        "kda", "kda", "kda", "mla", "kda"]
    assert [mcfg.layer_is_moe(i) for i in range(1, 6)] == [
        False, True, True, True, True]
    assert mcfg.kda_index == {1: 0, 2: 1, 3: 2, 5: 3}
    assert mcfg.mla_index == {4: 0} and mcfg.n_moe_layers == 4
    assert mcfg.experts_held == (0, 4) and mcfg.num_experts == 8
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(mcfg, experts_held=(6, 4))
    with pytest.raises(ValueError, match="exactly one"):
        dataclasses.replace(mcfg, kda_layers=(1, 2, 3))
    full = kl.KimiLinearConfig()
    assert full.num_hidden_layers == 27 and len(full.kda_index) == 20
    assert full.latent_width == 576 and full.kda_width == 4096


def test_reference_lists_leaves_by_layer_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/2/mlp/experts/gate_proj"] == (4, 64, 32)
    assert shapes["layers/2/mlp/gate/kernel"] == (64, 8)
    assert shapes["layers/1/mlp/gate_proj/kernel"] == (64, 128)
    assert shapes["layers/4/self_attn/kv_b_proj/kernel"] == (24, 2 * 32)
    assert "layers/4/self_attn/A_log" not in shapes
    assert shapes["layers/5/self_attn/A_log"] == (2,)
    assert shapes["lm_head/kernel"] == (64, 96)
    with pytest.raises(ValueError, match="n_heads"):
        ref.logits_at({}, None, None, n_heads=5)
