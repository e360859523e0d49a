"""MiMo-V2 at a small size on the CPU: the program (``models/mimo_v2.py``,
the shared attention functions of ``models/nemotron_h.py`` and
``models/laguna.py``, ``parallel/expert.py``, ``ServingEngine`` through its
family seam) against the plain reference (``benchmarks/refs/mimo_v2_ref.py``),
which follows the published equations.

The tiny size keeps every inequality of the published one: a key head (24)
wider than a value head (16), 8 of 24 dimensions rotated (``int(24 x 0.334)``),
8 query heads on 2 key-value heads in a full layer and on 4 in a window
layer, a window of 16 that is one page of 16 (a ring of one block), 16
experts top-4 with a correction bias of which a share of 4 is held, the
first seven entries of the published pattern (full + dense, four window, a
full, a window); float32 parameters, so the program and the reference may
differ by rounding order only. Sinks are drawn around 1 with a spread of 1
(the benchmark's are around 0: there a sink takes a 129th of a full
window's mass), so that leaving them out shows."""

import dataclasses
import functools
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import (
    keye_ref,
    kimi_linear_ref,
    laguna_ref,
    nemotron_h_ref,
)
from benchmarks.refs import mimo_v2_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families import keye as keye_family
from deepspeed_tpu.inference.serving.families import (
    kimi_linear as kimi_family,
)
from deepspeed_tpu.inference.serving.families import laguna as laguna_family
from deepspeed_tpu.inference.serving.families import mimo_v2 as mimo_family
from deepspeed_tpu.inference.serving.families import (
    nemotron_h as nemotron_family,
)
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import laguna as lg
from deepspeed_tpu.models import mimo_v2 as mm
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod
from tests.unit import test_laguna, test_nemotron_h, test_step_fusion

PATTERN = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]     # the published one's start
CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 7, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
    "swa_head_dim": 24, "swa_v_head_dim": 16, "sliding_window": 16,
    "hybrid_layer_pattern": PATTERN, "moe_layer_freq": [0] + [1] * 11,
    "partial_rotary_factor": 0.334, "rope_theta": 10000000,
    "swa_rope_theta": 10000, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "layernorm_epsilon": 1e-5,
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": None,
    "norm_topk_prob": True, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "max_position_embeddings": 4096,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "share": {"n_routed_experts_published": 16, "experts_first": 4},
}
ROW = 16                          # a page, a prefill row and the window
W = CFG["sliding_window"]
PUBLISHED = 16                    # experts the router scores

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile"))


def model_config(cfg=CFG, **over):
    share = cfg["share"]
    return mm.MiMoV2Config.from_dict(
        dict(cfg, n_routed_experts=share["n_routed_experts_published"],
             **over),
        experts_held=(share["experts_first"], cfg["n_routed_experts"]))


@functools.lru_cache(maxsize=None)
def _weights(held, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    flat = weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, n_routed_experts=held)), seed,
        jnp.float32)
    return {k: (1.0 + 50.0 * v if k.endswith("attention_sink_bias") else v)
            for k, v in flat.items()}


def make(seed=7):
    flat = _weights(CFG["n_routed_experts"], seed)
    ref.bind(CFG)
    return flat, weights_mod.nest(flat), model_config()


PAD_T = 224


@functools.partial(jax.jit, static_argnames=("scale",))
def _reference_pass(flat, ids, scale=CFG["attention_value_scale"]):
    return ref.logits_at(flat, ids, jnp.arange(ids.shape[1])[None],
                         dims=dict(ref.dims_of(CFG), value_scale=scale))


def reference_logits(flat, ids, **kw):
    """[T, V] logits of one full forward pass over ``ids [T]`` (padded to
    one length so that the reference compiles once: it is causal, so what
    follows a position cannot reach it)."""
    row = np.zeros((1, PAD_T), np.int32)
    row[0, :len(ids)] = ids
    return np.asarray(_reference_pass(flat, jnp.asarray(row), **kw))[
        0, :len(ids)]


def engine(params, mcfg, **over):
    kw = dict(max_slots=3, max_queue=32, max_seq_len=256,
              prompt_buckets=(240,), kv_cache_dtype="fp32",
              kv_page_tokens=ROW, prefill_chunk_tokens=4 * ROW)
    kw.update(over)
    return ServingEngine(params, mcfg, ServingConfig(**kw))


def served_logits(eng, prompts, new):
    """Serve ``prompts`` to ``new`` tokens each; ``{request id: the logits
    of each decode step}`` beside the futures."""
    futs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    seen, occupants = {}, {}
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
    assert eng.drain(max_steps=3000) < 3000
    return futs, seen, occupants


def worst_gap(flat, futs, prompts, seen, new, **kw):
    worst = 0.0
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        want = reference_logits(flat, np.concatenate([p, toks]), **kw)
        for j, got in enumerate(seen[f.request_id][:new - 1]):
            worst = max(worst, float(np.abs(got - want[len(p) + j]).max()))
    return worst


# -- (a) prefill in chunks, then decode through the pool ---------------------

@pytest.mark.parametrize("call_rows", [1, 4])
def test_engine_logits_match_the_reference_forward_pass(call_rows):
    """Prompts shorter than the window (10), equal to it (a row, a ring),
    many windows long (150: the one-block ring is rewritten nine times in
    prefill and again in decode), one token past a row and past a call;
    chunk edges fall inside every prompt longer than a call; several
    prompts in one prefill call, several lanes at once, and more requests
    than lanes, so that lanes get a second occupant that must read nothing
    of the first though nothing is reset. A call of one row is one window:
    a row's window then reaches back into the ring as the call before left
    it; in a call of four rows it reaches the row before it in the call.
    Every decode step's logits are compared, lane by lane, with the
    reference's one forward pass over the prompt and the tokens served so
    far. Tolerance 2e-4 on logits of spread ~0.15: float32 rounding order
    reads under 1e-6 here, a bfloat16 anywhere on the way (a cached key, a
    router score) some 1e-3."""
    flat, params, mcfg = make()
    call = call_rows * ROW
    eng = engine(params, mcfg, prefill_chunk_tokens=call)
    assert isinstance(eng.family, mimo_family.MiMoV2Family)
    assert (eng.family.rows, eng.family.row_tokens) == (call_rows, ROW)
    rng = np.random.default_rng(1)
    lengths = (10, ROW, 150, W + 1, call + 3, 5, 70, 2 * ROW)
    new = 40                       # more than a ring: decode wraps it too
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in lengths]
    futs, seen, occupants = served_logits(eng, prompts, new)
    assert max(len(v) for v in occupants.values()) >= 2   # a lane was reused
    assert eng.pool.slot_resets == 0                       # and never reset
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        want = reference_logits(flat, np.concatenate([p, toks]))
        # the first token comes from the prefill program
        assert toks[0] == int(want[len(p) - 1].argmax())
        assert toks[1:] == [int(want[len(p) + j].argmax())
                            for j in range(new - 1)]
    assert worst_gap(flat, futs, prompts, seen, new) < 2e-4
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == sum(lengths)
    assert snap["moe_layer_steps"] == 6 * (snap["decode_steps"] - 1)
    assert 0 < snap["moe_experts_touched"] <= snap["moe_picks_here"]
    # a share of 4 of 16: a quarter of the picks, give or take, falls here
    picks = 4 * 6 * snap["tokens_emitted"]
    assert 0.1 * picks < snap["moe_picks_here"] < 0.5 * picks
    assert snap["decode_context_tokens"] >= sum(
        (new - 1) * n for n in lengths)
    # five window layers, and no lane's ring holds more than the window
    assert 0 < snap["decode_ring_positions"] <= (
        5 * W * snap["tokens_emitted"] + 5 * W * 3)
    assert snap["page_waits"] == 0


def test_the_ring_counter_counts_what_a_hand_made_step_owes(monkeypatch):
    """Three lanes at positions 4, 15 and 300 in five window layers of 16:
    5 x (5 + 16 + 16) ring positions behind the masks, from the host's
    mirror of the positions alone."""
    from types import SimpleNamespace

    from deepspeed_tpu.inference.serving.families import slot_state
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    monkeypatch.setattr(slot_state.SlotStateFamily, "decode_step",
                        lambda self, guard: ((), (), 0, 0))
    fam = mimo_family.MiMoV2Family(model_config())
    fam.paged_attn_layers, fam.ring_layers = 2, 5
    metrics = ServingMetrics()
    fam.loop = SimpleNamespace(
        pool=SimpleNamespace(positions=np.array([4, 15, 77, 300]),
                             page_tokens=ROW, pages_in_use=7),
        metrics=metrics,
        lanes=SimpleNamespace(requests={0: None, 1: None, 3: None}))
    fam.decode_step(None)
    snap = metrics.snapshot()
    assert snap["decode_ring_positions"] == 5 * (5 + 16 + 16)
    assert snap["decode_context_tokens"] == 4 + 15 + 300
    assert snap["pool_pages_in_use_steps"] == 7


# -- (b) the sink -------------------------------------------------------------

def _window_layer(sink, positions=(0, 3, 15, 40)):
    """A window layer's decode output for four lanes over random rings,
    with the given sinks ``[8]`` (None: no sink)."""
    _, params, mcfg = make()
    p = dict(params["layers"]["1"]["self_attn"])
    shape = mcfg.attention(1)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(5, 4, 1, 4 * 24, ROW)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(5, 4, 1, 4 * 16, ROW)), jnp.float32)
    how = dict(window=W, rotate=pl.rotary(mcfg.rope(1), shape, "rope_window"))
    if sink is not None:
        how["sink"] = jnp.asarray(sink, jnp.float32).reshape(4, 2)
    y, wk2, wv2 = pl.window_decode(
        p, shape, x, wk, wv, 2, jnp.asarray(positions, jnp.int32),
        jnp.ones(4, bool), **how)
    return np.asarray(y), p, x, np.asarray(wk2[2]), np.asarray(wv2[2])


def _by_hand(p, x, keys, values, positions, sink, sink_value=None):
    """The layer by hand in numpy from the rings as the step left them:
    softmax over the held keys and one more column a head, the sink, which
    weighs ``sink_value`` (None: nothing, as published)."""
    mcfg = model_config()
    shape = mcfg.attention(1)
    q, _, _ = pl.gqa_project(p, shape, x)
    q, _ = pl.rotary(mcfg.rope(1), shape, "r")(
        q, jnp.zeros((4, 4 * 24)), jnp.asarray(positions))
    q = np.asarray(q)                                   # [B, 4, 2, 24]
    out = np.zeros((4, 8, 16))
    for b, pos in enumerate(positions):
        held = [j for j in range(W) if pos - (pos - j) % W >= 0]
        for g in range(4):
            k = keys[b, 0, g * 24:(g + 1) * 24][:, held]        # [24, n]
            v = values[b, 0, g * 16:(g + 1) * 16][:, held]      # [16, n]
            for j in range(2):
                s = q[b, g, j] @ k / np.sqrt(24.0)
                s = np.append(s, sink[2 * g + j])
                e = np.exp(s - s.max())
                pr = e / e.sum()
                out[b, 2 * g + j] = v @ pr[:-1]
                if sink_value is not None:
                    out[b, 2 * g + j] += pr[-1] * sink_value
    return out.reshape(4, 128) @ np.asarray(p["o_proj"]["kernel"])


def test_the_sink_takes_mass_and_adds_no_value():
    """Random sinks: the layer equals the softmax with one more column a
    head, dropped afterwards, worked by hand; and it fails to equal both the
    layer without a sink and a sink that is given a value (a column that is
    kept), at positions where the window is nearly empty (0, 3) and where it
    is full (15, 40)."""
    positions = (0, 3, 15, 40)
    sink = np.random.default_rng(2).normal(size=8) + 1.0
    y, p, x, keys, values = _window_layer(sink, positions)
    want = _by_hand(p, x, keys, values, positions, sink)
    np.testing.assert_allclose(y, want, atol=2e-5)
    plain, *_ = _window_layer(None, positions)
    assert np.abs(plain - want).max() > 100 * 2e-5        # left out: shows
    valued = _by_hand(p, x, keys, values, positions, sink,
                      sink_value=np.ones(16))
    assert np.abs(valued - want).max() > 100 * 2e-5       # given a value


def test_a_sink_far_below_every_score_is_a_plain_softmax():
    gone, *_ = _window_layer(np.full(8, -1e30))
    plain, *_ = _window_layer(None)
    np.testing.assert_allclose(gone, plain, atol=1e-6)


@pytest.mark.parametrize("broken", ["no_sink", "rotate_all"])
def test_the_engine_against_the_reference_fails_when_a_part_is_left_out(
        broken, monkeypatch):
    """Acceptance: leaving out the sink, or rotating all 24 dimensions of a
    head where 16 are passed through, moves the served logits by far more
    than the tolerance of (a): ten times it and more (with weights of 0.02 the
    scores are small, so a wrong rotation moves the logits by 5e-3 and not
    by their spread). The value scale has its own test below."""
    flat, params, mcfg = make()
    if broken == "no_sink":
        mcfg = dataclasses.replace(mcfg, add_swa_attention_sink_bias=False)
    else:
        mcfg = dataclasses.replace(mcfg, partial_rotary_factor=1.0)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (20, 50)]
    futs, seen, _ = served_logits(engine(params, mcfg), prompts, 6)
    assert worst_gap(flat, futs, prompts, seen, 6) > 10 * 2e-4


# -- (c) the value scale, once -----------------------------------------------

@pytest.mark.parametrize("program, reference, agree", [
    (0.707, 0.707, True), (1.0, 0.707, False),
    (0.707 ** 2, 0.707, False), (0.5, 0.5, True)])
def test_the_value_scale_is_applied_exactly_once(program, reference, agree):
    """The program multiplies the context and caches values as ``v_proj``
    gives them; the reference multiplies the values. With the same scale
    they agree; a program that leaves the scale out (1.0) or applies it on
    both sides of the cache (its square) does not."""
    flat, params, mcfg = make()
    mcfg = dataclasses.replace(mcfg, attention_value_scale=program)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (20, 50)]
    futs, seen, _ = served_logits(engine(params, mcfg), prompts, 6)
    gap = worst_gap(flat, futs, prompts, seen, 6, scale=reference)
    assert (gap < 2e-4) if agree else (gap > 10 * 2e-4), gap


def test_the_cache_holds_keys_and_values_of_their_own_widths():
    """After a prompt of 20 tokens: a full layer's page holds rotated keys
    of 2 x 24 values and unscaled values of 2 x 16 a token, a window
    layer's ring (one block) the last 16 positions at ``p % 16`` with 4 x 24
    and 4 x 16."""
    flat, params, mcfg = make()
    eng = engine(params, mcfg, max_slots=1)
    prompt = np.random.default_rng(13).integers(0, 96, 20).astype(np.int32)
    eng.submit(prompt, max_new_tokens=4)
    for _ in range(20):             # until the prompt is read: it holds a lane
        if eng.lanes.requests:
            break
        eng.step()
    assert eng.lanes.requests
    st = eng.pool.state
    h = params["embed_tokens"]["embedding"][prompt]
    lp = params["layers"]["0"]
    x = pl.rms_norm(h, lp["input_layernorm"]["scale"], 1e-5)
    v = x @ lp["self_attn"]["v_proj"]["kernel"]                  # [20, 32]
    k = pl.apply_rope(mcfg.rope(0), (
        x @ lp["self_attn"]["k_proj"]["kernel"]).reshape(20, 2, 24),
        jnp.arange(20)).reshape(20, 48)
    table = eng.pool.page_tables[0]
    for pos in (0, 15, 16, 19):
        page, col = table[pos // ROW], pos % ROW
        np.testing.assert_allclose(st["v"][0, page, :, col], v[pos],
                                   atol=1e-6)
        np.testing.assert_allclose(st["k"][0, page, :, col], k[pos],
                                   atol=1e-5)
    assert st["k"].shape[2:] == (48, ROW) and st["v"].shape[2:] == (32, ROW)
    assert st["wk"].shape[2:] == (1, 96, ROW)
    assert st["wv"].shape[2:] == (1, 64, ROW)
    # 8 of a head's 24 dimensions are rotated, 16 passed through
    inv, r = pl.rope_inv_freq(mcfg.rope(1), 24)
    assert r == 8 and inv.shape == (4,)
    np.testing.assert_allclose(inv, 10000.0 ** (-np.arange(4) / 4),
                               rtol=1e-12)
    np.testing.assert_allclose(pl.rope_inv_freq(mcfg.rope(0), 24)[0][1],
                               1e7 ** -0.25, rtol=1e-12)
    assert pl.rope_inv_freq(mm.MiMoV2Config().rope(0), 192)[1] == 64


# -- (c2) what one decode step writes ----------------------------------------

@functools.partial(jax.jit, static_argnames=("l",))
def reference_cache(flat, ids, l):
    """Layer ``l``'s keys (rotated) and values (unscaled, as the program
    caches them) at every position of ``ids [T]`` as the reference computes
    them: its own ``hidden_states`` cut at ``l`` layers is the layer's
    input. ``(k [T, KV * 24], v [T, KV * 16])``."""
    D = ref.dims_of(CFG)
    kind = "swa" if D["window_layer"][l] else "full"
    _, g, hd, _ = D[kind]
    h = ref.hidden_states(flat, ids, dict(D, layers=l))
    x = ref._rms(h, flat[f"layers/{l}/input_layernorm/scale"], D["eps"])
    k = ref.rope((x @ flat[f"layers/{l}/self_attn/k_proj/kernel"]).reshape(
        len(ids), g, hd), jnp.arange(len(ids)), D["theta_" + kind],
        D["rotary_factor"])
    return (k.reshape(len(ids), -1),
            x @ flat[f"layers/{l}/self_attn/v_proj/kernel"])


@pytest.mark.parametrize("page", [16, 8])
def test_a_decode_step_writes_one_column_a_lane_and_nothing_else(page):
    """A ring of one block (pages of 16, as published: window = page) and
    of two; keys and values of their own widths in all four arrays."""
    flat, params, mcfg = make()
    test_laguna.check_what_one_decode_step_writes(
        mm, params, mcfg, mcfg.cache_widths, page,
        lambda ids, l: reference_cache(flat, ids, l))


# -- (d) the share tied to the model ------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_references_expert_layer():
    """16 experts top-4 with a correction bias, in four shares of four: each
    share equals the reference's own share, the picks of all four are every
    pick, and their sum is the reference's layer with all 16 held. The bias
    chooses (without it other experts are picked) and does not weigh."""
    flat = _weights(PUBLISHED, 7)
    D = ref.dims_of(dict(CFG, n_routed_experts=PUBLISHED,
                         share={"n_routed_experts_published": PUBLISHED}))
    m = {k[len("layers/2/mlp/"):]: v for k, v in flat.items()
         if k.startswith("layers/2/mlp/")}
    # a bias wide enough to change the choice
    m["gate/e_score_correction_bias"] = 10.0 * m[
        "gate/e_score_correction_bias"]
    mlp = weights_mod.nest(m)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    want = jax.jit(lambda m, x: ref.expert_ffn(m, x, D, "f32"))(m, x)
    total, picks = jnp.zeros_like(x), 0
    for first in (0, 4, 8, 12):
        part = dict(mlp, experts={k: v[first:first + 4]
                                  for k, v in mlp["experts"].items()})
        y_part, stats = moe_ffn(part, x, k=4, scaling=1.0, renormalize=True,
                                held=(first, 4), tile=8)
        m_part = dict(m, **{f"experts/{n}": part["experts"][n]
                            for n in part["experts"]})
        want_part = jax.jit(lambda m, x, first=first: ref.expert_ffn(
            m, x, D, "f32", held=(first, 4)))(m_part, x)
        np.testing.assert_allclose(y_part, want_part, atol=2e-6, rtol=2e-5)
        total = total + y_part
        picks += int(stats[0])
    assert picks == 50 * 4
    np.testing.assert_allclose(total, want, atol=5e-6, rtol=5e-5)
    idx, wt = ref.route(m, x, D)
    unbiased, _ = ref.route(dict(m, **{"gate/e_score_correction_bias":
                                       jnp.zeros(16)}), x, D)
    assert (np.sort(idx, -1) != np.sort(unbiased, -1)).any()
    s = jax.nn.sigmoid(x @ m["gate/kernel"])
    np.testing.assert_allclose(
        wt, np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
        / np.take_along_axis(np.asarray(s), np.asarray(idx), -1).sum(
            -1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("every_expert, read", [
    (False, {1.0, 3.0}), (True, {0.0, 1.0, 2.0, 3.0})])
def test_a_decode_step_reads_every_held_expert_and_gives_the_same_sum(
        monkeypatch, every_expert, read):
    """``every_expert`` gives an expert nothing picked a tile of empty rows:
    the loop reads each held expert's weights (seen here by a mark in each
    one's first weight), the layer's sum and its three counts are what they
    were, and ``decode_step`` asks for it (its step then takes the same time
    whatever the router chose)."""
    rng = np.random.default_rng(11)
    experts = {n: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
               for n, s in (("gate_proj", (4, 64, 32)), ("up_proj", (4, 64, 32)),
                            ("down_proj", (4, 32, 64)))}
    experts["up_proj"] = experts["up_proj"].at[:, 0, 0].set(jnp.arange(4.0))
    x = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
    # held experts are 4-7: tokens pick 5 and 7 of them, and others elsewhere
    idx = jnp.asarray([[5, 0], [7, 5], [12, 7], [5, 1], [2, 3], [7, 9]])
    wt = jnp.asarray(rng.uniform(0.1, 1.0, size=(6, 2)), jnp.float32)
    seen = []
    real = expert_mod.expert_function

    def marked(weights):
        apply = real(weights)

        def fn(rows, get):
            jax.debug.callback(lambda m: seen.append(float(m)),
                               get("up_proj")[0, 0])
            return apply(rows, get)
        return fn

    plain, counts = expert_mod.held_experts_ffn(x, experts, idx, wt, (4, 4), 2)
    monkeypatch.setattr(expert_mod, "expert_function", marked)
    y, stats = expert_mod.held_experts_ffn(x, experts, idx, wt, (4, 4), 2,
                                           every_expert=every_expert)
    jax.effects_barrier()
    assert set(seen) == read
    np.testing.assert_array_equal(y, plain)
    assert stats.tolist() == counts.tolist() == [6, 2, 3]


def test_decode_asks_for_every_expert_and_prefill_does_not(monkeypatch):
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    asked = []
    real = expert_mod.held_experts_ffn

    def spy(*args, **kw):
        asked.append(args[7] if len(args) > 7 else kw.get("every_expert",
                                                          False))
        return real(*args, **kw)

    monkeypatch.setattr(expert_mod, "held_experts_ffn", spy)
    B, mp = eng.pool.max_slots, eng.pool.page_tables.shape[1]
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    jax.eval_shape(
        lambda p, *a: mm.decode_step(p, mcfg, *a, page_tokens=ROW), params,
        eng.pool.state, i32(B), i32(B), jnp.ones(B, bool), i32((B, mp)))
    assert asked == [True] * mcfg.n_moe_layers
    del asked[:]
    jax.eval_shape(
        lambda p, *a: mm.prefill_chunk(p, mcfg, *a, page_tokens=ROW), params,
        eng.pool.state, i32((4, ROW)), i32(4), i32(4), i32(4), i32((4, mp)))
    assert asked == [False] * mcfg.n_moe_layers


# -- (e) each unsupported option raises, by name ------------------------------

UNSUPPORTED = {
    "prefix_cache_mb": dict(prefix_cache_mb=4.0),
    "prefix_spill_mb": dict(prefix_cache_mb=4.0, prefix_spill_mb=1.0),
    "speculative_k": dict(speculative_k=2),
    "kv_cache_dtype='int8'": dict(kv_cache_dtype="int8"),
    "kv_cache_dtype='bf16'": dict(kv_cache_dtype="bf16"),
    "attention_impl='flash'": dict(attention_impl="flash"),
    "attention_kernel": dict(attention_kernel="xla"),
    "mesh_shape": dict(mesh_shape=(1, 2)),
    "partition_rules": dict(partition_rules=((".*", (None,)),)),
    "prefill_chunk_tokens=0": dict(prefill_chunk_tokens=0),
    "prefill_chunk_tokens=40": dict(prefill_chunk_tokens=40),
    # a page that does not divide the window
    "kv_page_tokens=32": dict(kv_page_tokens=32, prefill_chunk_tokens=64),
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
    assert "mimo_v2" in str(err.value) and "laguna" not in str(err.value)


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


@pytest.mark.parametrize("key, value, named", [
    ("add_full_attention_sink_bias", True, "add_full_attention_sink_bias"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("n_group", 8, "n_group"), ("topk_group", 4, "topk_group"),
    ("n_shared_experts", 1, "n_shared_experts"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope_scaling"),
    ("hybrid_layer_pattern", [0, 1, 1], "names 3 layers"),
    ("moe_layer_freq", [0, 2, 1, 1, 1, 1, 1], "0 or 1"),
    ("swa_num_key_value_heads", 3, "key-value"),
])
def test_the_configuration_refuses_what_the_program_does_not_compute(
        key, value, named):
    with pytest.raises(ValueError, match=named):
        model_config(**{key: value})


def test_config_reads_the_published_keys_up_to_the_depth():
    mcfg = model_config()
    assert [mcfg.is_window(l) for l in range(7)] == [
        False, True, True, True, True, False, True]
    assert [mcfg.is_moe(l) for l in range(7)] == [False] + [True] * 6
    assert mcfg.full_index == {0: 0, 5: 1}
    assert mcfg.window_index == {1: 0, 2: 1, 3: 2, 4: 3, 6: 4}
    assert mcfg.n_moe_layers == 6 and mcfg.experts_held == (4, 4)
    assert mcfg.attention(0) == pl.AttentionShape(8, 2, 24, 16)
    assert mcfg.attention(1) == pl.AttentionShape(8, 4, 24, 16)
    assert mcfg.cache_widths == {"k": 48, "v": 32, "wk": 96, "wv": 64}
    hash(mcfg)                                # a static argument of the jit
    full = mm.MiMoV2Config()
    assert full.num_hidden_layers == 48
    assert (len(full.full_index), len(full.window_index),
            full.n_moe_layers) == (9, 39, 47)
    assert sorted(full.full_index) == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert full.cache_widths == {"k": 768, "v": 512, "wk": 1536, "wv": 1024}
    assert full.experts_held == (0, 256)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(full, experts_held=(250, 16))


def test_the_pool_holds_four_arrays_of_four_widths_by_description():
    _, params, mcfg = make()
    eng = engine(params, mcfg, kv_pool_tokens=512)
    assert type(eng.pool) is HybridStatePool
    st = eng.pool.state
    assert st["k"].shape == (2, 512 // ROW + 1, 2 * 24, ROW)
    assert st["v"].shape == (2, 512 // ROW + 1, 2 * 16, ROW)
    assert st["wk"].shape == (5, 3, 1, 4 * 24, ROW)
    assert st["wv"].shape == (5, 3, 1, 4 * 16, ROW)
    assert eng.pool.paged_names == ("k", "v")
    assert eng.pool.slot_names == ("wk", "wv") and eng.pool.reset_names == ()


def test_reference_lists_leaves_by_layer_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/0/self_attn/q_proj/kernel"] == (64, 8 * 24)
    assert shapes["layers/0/self_attn/k_proj/kernel"] == (64, 2 * 24)
    assert shapes["layers/0/self_attn/v_proj/kernel"] == (64, 2 * 16)
    assert shapes["layers/1/self_attn/k_proj/kernel"] == (64, 4 * 24)
    assert shapes["layers/1/self_attn/v_proj/kernel"] == (64, 4 * 16)
    assert shapes["layers/1/self_attn/o_proj/kernel"] == (8 * 16, 64)
    assert shapes["layers/1/self_attn/attention_sink_bias"] == (8,)
    assert "layers/0/self_attn/attention_sink_bias" not in shapes
    assert "layers/5/self_attn/attention_sink_bias" not in shapes
    assert shapes["layers/0/mlp/up_proj/kernel"] == (64, 96)
    assert shapes["layers/1/mlp/experts/gate_proj"] == (4, 64, 32)
    assert shapes["layers/1/mlp/gate/kernel"] == (64, 16)
    assert shapes["layers/1/mlp/gate/e_score_correction_bias"] == (16,)
    assert shapes["lm_head/kernel"] == (64, 96)
    assert not any(k.startswith("layers/7/") for k in shapes)
    with pytest.raises(ValueError, match="n_heads"):
        ref.logits_at({}, None, None, n_heads=5)


def test_background_loop_streams_tokens():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    got = []
    eng.start()
    try:
        fut = eng.submit(np.arange(50) % 96, max_new_tokens=5,
                         stream_cb=lambda rid, tok: got.append(tok))
        assert fut.result(timeout=120) == got and len(got) == 5
    finally:
        eng.stop()


# -- (f) the ten slot-state programs are the text they were -------------------

ROWS = 4
# sha256 (first 16 hex digits) of ``jit(...).lower(...).as_text()`` of every
# slot-state family's two programs at the sizes of the family's own unit
# test, with jax 0.9.0. The text carries no names or locations, so a
# refactor that traces the same operations in the same order keeps it: a
# change to a function several families share (the paged grouped-query
# attention, the window ring, the expert layer, the pool's description)
# shows here which programs it reached. A change that is MEANT to alter
# these programs, or a jax that prints them otherwise, reads them anew (the
# test's message prints them). When each was last read: Laguna's and
# Nemotron-H's prefill at 61d9b87 (PR 39), their decode in PR 41, which was
# meant to alter them (one write a layer and array); MiMo-V2's decode at
# e6120d2 (PR 41), Keye-VL's decode, off the TPU where ``attend_tiles`` is
# the two products it was, at 1f76e72 (PR 43); MiMo-V2's and Keye-VL's
# prefill and Kimi-Linear's two at 4ba738f (PR 44).
PARENT_TEXT = {
    "keye_decode": "e322090161d07b3e",
    "keye_prefill": "901fee62511254c4",
    "kimi_decode": "18215d53219d7b72",
    "kimi_prefill": "6da4795d13526e68",
    "laguna_decode": "b837a006efe47cca",
    "laguna_prefill": "a6705ed92545bbca",
    "mimo_decode": "b17958c3588aa8fb",
    "mimo_prefill": "9c18ea5aec243200",
    "nemotron_decode": "d441420ad3e1c1bc",
    "nemotron_prefill": "483ba7f75d40b702",
}


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _lowered(which):
    """``<family>_<program>`` traced at the tiny shapes, not compiled."""
    # at call time: both import this module for what they share with it
    from tests.unit import test_keye, test_kimi_linear

    family, program = which.split("_")
    slots, pages, mp, rows, row = 3, 9, 4, ROWS, ROW
    if family == "laguna":
        cfg = lg.LagunaConfig.from_dict(test_laguna.CFG)
        shapes = laguna_ref.weight_shapes(test_laguna.CFG)
        state = {"k": _sds((2, pages, 32, ROW)),
                 "v": _sds((2, pages, 32, ROW)),
                 "wk": _sds((3, slots, 2, 32, ROW)),
                 "wv": _sds((3, slots, 2, 32, ROW))}
        fns = (laguna_family._laguna_decode_step_jit,
               laguna_family._laguna_prefill_chunk_jit)
    elif family == "mimo":
        cfg = model_config()
        shapes = ref.weight_shapes(CFG)
        state = {name: _sds(((5, slots, 1) if name[0] == "w" else (2, pages))
                            + (width, ROW))
                 for name, width in cfg.cache_widths.items()}
        fns = (mimo_family._mimo_decode_step_jit,
               mimo_family._mimo_prefill_chunk_jit)
    elif family == "nemotron":
        cfg = test_nemotron_h.model_config(test_nemotron_h.CFG)
        shapes = nemotron_h_ref.weight_shapes(test_nemotron_h.CFG)
        state = {"ssm": _sds((4, slots, 4, 16, 16)),
                 "conv": _sds((4, slots, 3, cfg.conv_dim)),
                 "k": _sds((1, pages, 32, ROW)),
                 "v": _sds((1, pages, 32, ROW))}
        fns = (nemotron_family._nemotron_decode_step_jit,
               nemotron_family._nemotron_prefill_chunk_jit)
    elif family == "keye":
        cfg = test_keye.model_config()
        shapes = keye_ref.weight_shapes(test_keye.CFG)
        pages, mp = 49, 16
        state = {"kv": _sds((3, pages, ROW, 4, 16)),
                 "ik": _sds((3, pages, 8, ROW))}
        fns = (keye_family._keye_decode_step_jit,
               keye_family._keye_prefill_chunk_jit)
    else:
        cfg = test_kimi_linear.model_config(test_kimi_linear.CFG)
        shapes = kimi_linear_ref.weight_shapes(test_kimi_linear.CFG)
        rows, row = 1, test_kimi_linear.CHUNK   # a prompt's next chunk a call
        state = {"kda": _sds((4, slots, 2, 16, 16)),
                 "conv": _sds((4, slots, 3, 3 * cfg.kda_width)),
                 "latent": _sds((1, pages, cfg.latent_width, ROW))}
        fns = (kimi_family._kimi_decode_step_jit,
               kimi_family._kimi_prefill_chunk_jit)
    i32 = jnp.int32
    args = ((_sds((slots,), i32), _sds((slots,), i32),
             _sds((slots,), jnp.bool_), _sds((slots, mp), i32))
            if program == "decode" else
            (_sds((rows, row), i32), _sds((rows,), i32), _sds((rows,), i32),
             _sds((rows,), i32), _sds((rows, mp), i32)))
    params = weights_mod.nest({k: _sds(v) for k, v in shapes.items()})
    return state, fns[program == "prefill"].lower(
        params, state, *args, cfg=cfg, page_tokens=ROW, keep_logits=False)


@pytest.mark.parametrize("which", sorted(PARENT_TEXT))
def test_the_slot_state_programs_lowered_are_the_parents_text(which):
    """What a family's jitted program traces, operation for operation, is
    what it traced when ``PARENT_TEXT`` was read."""
    text = _lowered(which)[1].as_text()
    assert "stablehlo" in text and len(text) > 100000
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == PARENT_TEXT[which], (which, got)


@pytest.mark.parametrize("which", ["laguna_decode", "mimo_decode",
                                   "nemotron_decode"])
def test_no_loop_of_a_decode_program_writes_a_pool_array(which):
    """A decode step's new keys and values reach a ring or a page in one
    operation a layer and array (a whole layer's blocks at a static index,
    or one scatter of whole blocks), never lane by lane: in the program as
    traced (a ring of two blocks in Laguna's, of one in MiMo-V2's, pages
    alone in Nemotron-H's) no loop's body updates anything of a pool
    array's shape. The loops that are there (the work list's tiles, the
    experts' tiles) write buffers of their own."""
    state, lowered = _lowered(which)
    text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    pools = {"f32[" + ",".join(map(str, x.shape)) + "]"
             for name, x in state.items() if name in ("k", "v", "wk", "wv")}
    loops = test_step_fusion._loop_bodies(text)
    assert loops and any(shape in text for shape in pools)
    writes = [l for lines in loops for l in lines
              if re.search(r"\s(dynamic-update-slice|scatter)\(", l)]
    assert writes, "the work list's loop writes its partial sums"
    assert not [l for l in writes
                if l.split("=")[1].split()[0].split("{")[0] in pools]
