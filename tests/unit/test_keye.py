"""Keye-VL at a small size on the CPU: the program (``models/keye.py``, the
functions it shares with the other decoders, ``parallel/expert.py``'s
softmax router, ``ServingEngine`` through its family seam) against the plain
reference (``benchmarks/refs/keye_ref.py``), which follows the published
equations.

The tiny size keeps the shape of the published one: 8 query heads on 2
key-value heads of 16, an indexer of 4 heads of 8 on one key head, ``topk``
24 in pages of 16 (so that a context of a few pages already prunes, as
every context of the cell does), 16 experts top-4 of which a share of 4 is
held, three layers that are all alike; float32 parameters, so the program
and the reference may differ by rounding order only. With 4 indexer heads a
sixteenth of the index scores are exactly zero (every head's product
negative), so ties AT the threshold are common here, where at the published
16 heads they are one in 65,536: the order among equal scores is tested
whether it is meant to be or not."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import keye_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families import keye as keye_family
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import keye as ky
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod
from tests.unit import test_mimo_v2

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 2, 4], "rope_type": "default",
                     "type": "default"},
    "num_experts": 4, "num_local_experts": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "sliding_window": None,
    "use_sliding_window": False, "attention_bias": False,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 24},
    "share": {"num_experts_published": 16, "experts_first": 4},
}
ROW = 16                          # a page and a prefill row
TOPK = CFG["sa_config"]["topk"]
PUBLISHED = 16                    # experts the router scores

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile", "scoring"))


def model_config(cfg=CFG, **over):
    share = cfg["share"]
    return ky.KeyeConfig.from_dict(
        dict(cfg, num_experts=share["num_experts_published"], **over),
        experts_held=(share["experts_first"], cfg["num_experts"]))


@functools.lru_cache(maxsize=None)
def _weights(held, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, num_experts=held)), seed, jnp.float32)


def make(seed=7):
    flat = _weights(CFG["num_experts"], seed)
    ref.bind(CFG)
    return flat, weights_mod.nest(flat), model_config()


PAD_T = 224


@functools.partial(jax.jit, static_argnames=("selection",))
def _reference_pass(flat, ids, selection=ref.select):
    return ref.logits_at(flat, ids, jnp.arange(ids.shape[1])[None],
                         dims=ref.dims_of(CFG), selection=selection)


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


served_logits = test_mimo_v2.served_logits


def worst_gap(flat, futs, prompts, seen, new, **kw):
    worst = 0.0
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        want = reference_logits(flat, np.concatenate([p, toks]), **kw)
        for j, got in enumerate(seen[f.request_id][:new - 1]):
            worst = max(worst, float(np.abs(got - want[len(p) + j]).max()))
    return worst


LENGTHS = (10, ROW, 150, ROW + 1, 4 * ROW + 3, 5, 70, 2 * ROW)
NEW = 30


@functools.lru_cache(maxsize=None)
def _served(call_rows):
    flat, params, mcfg = make()
    eng = engine(params, mcfg, prefill_chunk_tokens=call_rows * ROW)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in LENGTHS]
    futs, seen, occupants = served_logits(eng, prompts, NEW)
    return flat, eng, prompts, futs, seen, occupants


# -- (a) prefill in rows, then decode through the pages ----------------------

@pytest.mark.parametrize("call_rows", [1, 4])
def test_engine_logits_match_the_reference_forward_pass(call_rows):
    """Prompts shorter than ``topk`` (5, 10, a page of 16: the selection
    must then be every position and attention dense grouped-query
    attention), just over it (32 + decode), and several times it (67, 70,
    150: six times ``topk``, so that the selection prunes in prefill and in
    every decode step); chunk edges fall inside every prompt longer than a
    call; several prompts in one prefill call, several lanes at once, and
    more requests than lanes, so that lanes and pages get a second occupant
    that must read nothing of the first though nothing is reset. Every
    decode step's logits are compared, lane by lane, with the reference's
    one forward pass over the prompt and the tokens served so far.
    Tolerance 2e-4 on logits of spread ~0.15: float32 rounding order reads
    under 1e-6 here; one key selected otherwise reads 1e-3 to 1e-1 (the
    wrong-selection test below)."""
    flat, eng, prompts, futs, seen, occupants = _served(call_rows)
    assert isinstance(eng.family, keye_family.KeyeFamily)
    assert (eng.family.rows, eng.family.row_tokens) == (call_rows, ROW)
    assert max(len(v) for v in occupants.values()) >= 2   # a lane was reused
    assert eng.pool.slot_resets == 0                       # and never reset
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        want = reference_logits(flat, np.concatenate([p, toks]))
        # the first token comes from the prefill program
        assert toks[0] == int(want[len(p) - 1].argmax())
        assert toks[1:] == [int(want[len(p) + j].argmax())
                            for j in range(NEW - 1)]
    assert worst_gap(flat, futs, prompts, seen, NEW) < 2e-4
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == sum(LENGTHS)
    assert snap["moe_layer_steps"] == 3 * (snap["decode_steps"] - 1)
    assert 0 < snap["moe_experts_touched"] <= snap["moe_picks_here"]
    # a lane that holds p positions scores p + 1 keys a layer and attends
    # topk of them at most; a lane that retires was given one step more
    scored = 3 * sum(n + j + 1 for n in LENGTHS for j in range(NEW - 1))
    attended = 3 * sum(min(n + j + 1, TOPK) for n in LENGTHS
                       for j in range(NEW - 1))
    assert scored <= snap["dsa_keys_scored"] <= scored + 3 * 8 * 256
    assert attended <= snap["dsa_keys_attended"] <= attended + 3 * 8 * TOPK
    assert snap["dsa_keys_attended"] < 0.5 * snap["dsa_keys_scored"]
    assert snap["page_waits"] == 0
    # pages only: no slot holds state, no slot array is reported
    assert (snap["state_slots_in_use"], snap["state_pool_bytes"]) == (0, 0)
    assert snap["latent_pool_bytes"] == eng.pool.paged_bytes() > 0


def _first_positions(scores, qpos, topk):
    """A wrong selection: the first ``topk`` positions, not the best."""
    Ts = scores.shape[1]
    s = jnp.arange(Ts)[None, :]
    return (s <= qpos[:, None]) & (s < topk)


def _no_causal_bound(scores, qpos, topk):
    """A wrong selection: the best ``topk`` of ALL positions, those after
    the query too."""
    _, idx = jax.lax.top_k(scores, min(topk, scores.shape[1]))
    return jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)


def _rounded_scores(scores, qpos, topk):
    """A wrong selection: the scores rounded to fp8's three mantissa bits
    before the best are taken."""
    return ref.select(jax.lax.reduce_precision(scores, 4, 3), qpos, topk)


@pytest.mark.parametrize("wrong", [_first_positions, _no_causal_bound,
                                   _rounded_scores])
def test_the_engine_against_a_reference_that_selects_otherwise_fails(wrong):
    """The comparison that passes above at 2e-4 fails by orders of magnitude
    against a reference whose selection is wrong, which is to say that a
    program with that selection would fail against the right reference:
    the first ``topk`` positions, no causal bound, scores rounded to fp8."""
    flat, _, prompts, futs, seen, _ = _served(4)
    assert worst_gap(flat, futs, prompts, seen, NEW, selection=wrong) > 1e-2


# -- (b) the selection itself -------------------------------------------------

def _scores(seed, rows, keys, ties):
    s = np.random.default_rng(seed).normal(size=(rows, keys)).astype(
        np.float32)
    if ties:                # a third of the scores exactly 0, of either sign
        z = np.random.default_rng(seed + 1).random((rows, keys)) < 0.34
        s = np.where(z, np.where(s > 0, 0.0, -0.0), s).astype(np.float32)
    return s


@pytest.mark.parametrize("ties", [False, True])
def test_the_decode_selection_is_the_references_set(ties):
    """``select_topk`` against the reference's ``select`` on seeded scores:
    the same set a row, at contexts under, at and over ``topk``; with ties
    (zeros of both signs, a third of the row) the lower positions are
    taken."""
    s = _scores(3, 6, 128, ties)
    positions = np.array([0, 10, TOPK - 1, TOPK, 90, 127], np.int32)
    at, chosen = ky.select_topk(jnp.asarray(s), jnp.asarray(positions), TOPK)
    want = np.asarray(ref.select(jnp.asarray(s), jnp.asarray(positions),
                                 TOPK))
    at, chosen = np.asarray(at), np.asarray(chosen)
    for b, p in enumerate(positions):
        got = set(at[b][chosen[b]].tolist())
        assert len(got) == chosen[b].sum() == min(TOPK, p + 1)
        assert got == set(np.flatnonzero(want[b]).tolist())
        assert max(got) <= p                              # causal


@pytest.mark.parametrize("ties", [False, True])
def test_kth_largest_is_the_sorted_rows_kth(ties):
    s = _scores(5, 7, 96, ties)
    k = np.array([1, 2, 24, 50, 95, 96, 33], np.int32)
    u = ky._sortable(jnp.asarray(s))
    got = np.asarray(ky.kth_largest(u, jnp.asarray(k)))
    want = np.sort(s, axis=1)[np.arange(7), 96 - k]
    np.testing.assert_array_equal(got, np.asarray(ky._sortable(
        jnp.asarray(want))))
    # the two zeros are one number, and order is the floats'
    zeros = np.asarray(ky._sortable(jnp.asarray([-0.0, 0.0, -1e-30, 1e-30],
                                                jnp.float32)))
    assert zeros[0] == zeros[1] and zeros[2] < zeros[0] < zeros[3]
    assert int(ky._sortable(jnp.float32(-np.inf))) > ky._LOWEST


def _one_layer(x, n, rows=5):
    """Layer 0's attention over ``n`` positions of ``x``, by the prefill
    function (rows of one page, pages out of order) and by the reference."""
    flat, params, mcfg = make()
    D = ref.dims_of(CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(ref._sub(flat, "layers/0/self_attn/"), x[:n], D,
                             "f32")
    tables = np.zeros((rows, 16), np.int32)
    tables[:, :5] = [3, 7, 2, 9, 11]
    starts = np.arange(rows) * ROW
    lens = np.clip(n - starts, 0, ROW)
    state = (jnp.zeros((3, 20, ROW, 4, 16)), jnp.zeros((3, 20, 8, ROW)))
    y, kv, ik = ky.dsa_prefill(
        params["layers"]["0"]["self_attn"], mcfg, x.reshape(rows, ROW, -1),
        *state, 0, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(lens), ROW)
    return np.asarray(y).reshape(rows * ROW, -1)[:n], np.asarray(want), kv, ik


def test_prefill_takes_the_lower_positions_among_equal_scores():
    """One layer's prefill attention against the reference's where the
    threshold falls among equal scores: with 4 indexer heads one score in
    16 is exactly 0, and at positions 39 to 50 the 24th largest of the row
    is such a zero. The reference (``lax.top_k``) takes the lower
    positions; a threshold alone would take all the zeros or none."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5 * ROW, 64), jnp.float32)
    got, want, _, _ = _one_layer(x, 70)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the zeros are there, and the threshold falls on them in some row
    flat, _, _ = make()
    D = ref.dims_of(CFG)
    w = ref._sub(flat, "layers/0/self_attn/indexer/")
    with jax.default_matmul_precision("highest"):
        qI, kI, wI = ref.indexer_inputs(w, x[:70], D, ref.text_positions(70),
                                        "f32")
        s = np.asarray(ref.index_scores(qI, kI, wI, "f32"))
    causal = np.tril(np.ones((70, 70), bool))
    kth = [np.sort(s[t, :t + 1])[-min(TOPK, t + 1)] for t in range(70)]
    assert sum(k == 0.0 for k in kth) >= 3
    assert ((s == 0) & causal).mean() > 0.02


def test_a_stale_column_of_a_reused_page_is_never_selected():
    """Pages that hold what a previous occupant left (here: indexer keys
    and tiles a thousand times larger than any real one, in every page of
    the pool, the spare page too) are read behind the lane's position: a
    request served over them gives the logits it gives over zeros."""
    flat, params, mcfg = make()
    eng = engine(params, mcfg)
    rng = np.random.default_rng(2)
    for name, a in eng.pool.state.items():
        eng.pool.state[name] = jnp.asarray(
            1e3 * rng.normal(size=a.shape), a.dtype)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (40, 7, 75)]
    futs, seen, _ = served_logits(eng, prompts, 20)
    assert worst_gap(flat, futs, prompts, seen, 20) < 2e-4


def test_a_decode_step_fetches_selected_tiles_and_no_page_of_keys_and_values():
    """The decode program as traced: every read of the ``kv`` array is a
    gather of single tokens' tiles (slice sizes ``1, 1, 1, 2 KV, hd``), as
    many a layer as ``topk`` a lane, and none reads a page (``page_tokens``
    along the token axis); the indexer's pages are read whole."""
    _, params, mcfg = make()
    B, mp, pages = 3, 16, 49
    state = {"kv": jnp.zeros((3, pages, ROW, 4, 16)),
             "ik": jnp.zeros((3, pages, 8, ROW))}
    jaxpr = jax.make_jaxpr(
        lambda p, s, t, pos, act, tab: ky.decode_step(
            p, mcfg, s, t, pos, act, tab, page_tokens=ROW))(
        params, state, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, bool), jnp.zeros((B, mp), jnp.int32))
    kv_shape, reads = state["kv"].shape, []

    def walk(jp):
        for eqn in jp.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            if (eqn.primitive.name in ("gather", "dynamic_slice", "slice")
                    and eqn.invars[0].aval.shape == kv_shape):
                reads.append((eqn.primitive.name,
                              eqn.params.get("slice_sizes"),
                              eqn.outvars[0].aval.shape))

    walk(jaxpr.jaxpr)
    assert len(reads) == 3, reads                     # one a layer
    for name, sizes, out in reads:
        assert name == "gather" and tuple(sizes) == (1, 1, 1, 4, 16)
        assert out == (B, TOPK, 4, 16)


# -- (c) the expert layer -----------------------------------------------------

def test_the_softmax_router_is_the_references():
    flat, params, _ = make()
    D = ref.dims_of(CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
    w = ref._sub(flat, "layers/1/mlp/")
    with jax.default_matmul_precision("highest"):
        want_idx, want_w = ref.route(w, x, D)
    idx, wt = expert_mod.softmax_topk_routing(
        x, params["layers"]["1"]["mlp"]["gate"]["kernel"], 4)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(wt), np.asarray(want_w), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 1.0, rtol=1e-6)
    # not renormalised: the softmax's own mass, under 1 for 4 of 16
    _, raw = expert_mod.softmax_topk_routing(
        x, params["layers"]["1"]["mlp"]["gate"]["kernel"], 4,
        renormalize=False)
    assert (np.asarray(raw).sum(-1) < 1.0).all()
    np.testing.assert_allclose(np.asarray(raw) / np.asarray(raw).sum(
        -1, keepdims=True), np.asarray(wt), rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_references_expert_layer():
    """Eight chips that hold 2 of the 16 published experts each: what their
    expert layers give, each through ``routed_moe_ffn`` with its own
    ``held``, adds up to the reference's layer with all 16 (there is no
    shared expert to count once)."""
    flat = _weights(PUBLISHED, 11)
    D = dict(ref.dims_of(dict(CFG, num_experts=PUBLISHED)), held=(0, 16))
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 64), jnp.float32)
    w = ref._sub(flat, "layers/2/mlp/")
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.expert_ffn(w, x, D, "f32"))
    params = weights_mod.nest(flat)["layers"]["2"]["mlp"]
    total = np.zeros_like(whole)
    parts = []
    for first in range(0, 16, 2):
        share = dict(params, experts={
            k: v[first:first + 2] for k, v in params["experts"].items()})
        y, stats = moe_ffn(share, x, None, k=4, scaling=1.0,
                           renormalize=True, held=(first, 2), tile=8,
                           scoring="softmax")
        parts.append(np.asarray(y))
        total += parts[-1]
        # and the reference given the same share gives the same part
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(
                {**w, **{f"experts/{k}": v for k, v in
                         share["experts"].items()}}, x, D, "f32",
                held=(first, 2))
        np.testing.assert_allclose(parts[-1], np.asarray(part), atol=2e-6)
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert all(np.abs(p).max() > 1e-4 for p in parts)     # every share adds


def test_the_two_scoring_functions_go_through_one_expert_layer():
    """``routed_moe_ffn`` by ``scoring``: sigmoid is what it was, softmax
    refuses a correction bias and a scaling it does not have, anything else
    is refused by name."""
    _, params, _ = make()
    p = params["layers"]["0"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (9, 64), jnp.float32)
    kw = dict(k=4, renormalize=True, held=(4, 4), tile=8)
    soft, _ = moe_ffn(p, x, None, scaling=1.0, scoring="softmax", **kw)
    sig, _ = moe_ffn(p, x, None, scaling=1.0, scoring="sigmoid", **kw)
    assert np.abs(np.asarray(soft) - np.asarray(sig)).max() > 1e-5
    with pytest.raises(ValueError, match="scoring 'tanh'"):
        expert_mod.routed_moe_ffn(p, x, scaling=1.0, scoring="tanh", **kw)
    with pytest.raises(AssertionError):
        expert_mod.routed_moe_ffn(p, x, scaling=2.5, scoring="softmax", **kw)


def test_decode_asks_for_every_expert_and_prefill_does_not(monkeypatch):
    _, params, mcfg = make()
    asked = []
    real = expert_mod.routed_moe_ffn

    def spy(*a, **kw):
        asked.append((kw["every_expert"], kw["scoring"]))
        return real(*a, **kw)

    monkeypatch.setattr(expert_mod, "routed_moe_ffn", spy)
    state = {"kv": jnp.zeros((3, 9, ROW, 4, 16)),
             "ik": jnp.zeros((3, 9, 8, ROW))}
    i32 = jnp.int32
    ky.decode_step(params, mcfg, state, jnp.zeros(2, i32), jnp.zeros(2, i32),
                   jnp.ones(2, bool), jnp.ones((2, 4), i32), page_tokens=ROW)
    assert asked == [(True, "softmax")] * 3
    del asked[:]
    ky.prefill_chunk(params, mcfg, state, jnp.zeros((2, ROW), i32),
                     jnp.zeros(2, i32), jnp.zeros(2, i32),
                     jnp.full(2, ROW, i32), jnp.ones((2, 4), i32),
                     page_tokens=ROW)
    assert asked == [(False, "softmax")] * 3


# -- (d) positions ------------------------------------------------------------

def test_equal_position_triples_rotate_as_plain_rotary_positions():
    """The reference's M-RoPE with its three sections against plain RoPE,
    and against the program's ``apply_rope``: equal where a token's three
    components are equal (text), different where they are not (an image
    patch), for a head of 16 with sections (2, 2, 4) and the indexer's head
    of 8 with (1, 1, 2)."""
    rng = np.random.default_rng(6)
    for head in (16, 8):
        x = jnp.asarray(rng.normal(size=(12, 3, head)), jnp.float32)
        pos = jnp.arange(100, 112)
        triple = jnp.broadcast_to(pos, (3, 12))
        plain = ref.rope(x, pos, 1e7)
        np.testing.assert_allclose(
            np.asarray(ref.mrope(x, triple, 1e7, (2, 2, 4))),
            np.asarray(plain), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(pl.apply_rope(pl.RopeSpec(rope_theta=1e7), x, pos)),
            np.asarray(plain), atol=1e-5)
        patch = triple.at[1].add(3).at[2].add(7)
        assert np.abs(np.asarray(ref.mrope(x, patch, 1e7, (2, 2, 4)))
                      - np.asarray(plain)).max() > 1e-2
    assert ref.sections_for((16, 24, 24), 128) == (16, 24, 24)
    assert ref.sections_for((16, 24, 24), 64) == (8, 12, 12)
    with pytest.raises(ValueError, match="sections"):
        ref.sections_for((2, 3, 3), 8)


# -- (e) the family, the pool and what is refused -----------------------------

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
    assert "keye" in str(err.value)


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


@pytest.mark.parametrize("key, value, named", [
    ("sliding_window", 128, "sliding_window"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("vision_config", {"depth": 27}, "vision_config"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("rope_scaling", {"mrope_section": [2, 2, 2]}, "mrope_section"),
    ("sa_config", dict(CFG["sa_config"], indexer_num_kv_heads=2),
     "indexer_num_kv_heads"),
    ("sa_config", dict(CFG["sa_config"], topk=0), "topk"),
])
def test_the_configuration_refuses_what_the_program_does_not_compute(
        key, value, named):
    with pytest.raises(ValueError, match=named):
        model_config(**{key: value})


def test_config_reads_the_published_keys():
    mcfg = model_config()
    assert (mcfg.indexer_num_heads, mcfg.indexer_head_dim, mcfg.topk) == (
        4, 8, 24)
    assert (mcfg.q_chunk_size, mcfg.kv_chunk_size) == (512, 512)
    assert mcfg.mrope_section == (2, 2, 4)
    assert (mcfg.num_experts, mcfg.experts_held) == (16, (4, 4))
    assert mcfg.n_moe_layers == mcfg.num_hidden_layers == 3
    assert mcfg.cache_widths == {"kv": (4, 16), "ik": 8}
    hash(mcfg)                              # static under jit
    whole = ky.KeyeConfig()                 # the published numbers
    assert (whole.topk, whole.num_experts, whole.experts_held) == (
        2048, 128, (0, 128))
    assert whole.cache_widths == {"kv": (8, 128), "ik": 64}
    assert dataclasses.replace(whole, experts_held=(0, 16)).experts_held == (
        0, 16)
    with pytest.raises(ValueError, match="experts_held"):
        ky.KeyeConfig(experts_held=(120, 16))


def test_the_pool_holds_pages_only_by_description():
    """Two paged arrays behind one page table, described from
    ``cache_widths`` (a token's tile, tokens first; a width, tokens last),
    and no slot array: nothing to reset, nothing to report."""
    _, params, mcfg = make()
    eng = engine(params, mcfg, kv_pool_tokens=30 * ROW)
    pool = eng.pool
    assert type(pool) is HybridStatePool
    assert (pool.paged_names, pool.slot_names, pool.reset_names) == (
        ("kv", "ik"), (), ())
    assert {n: a.shape for n, a in pool.state.items()} == {
        "kv": (3, 31, ROW, 4, 16), "ik": (3, 31, 8, ROW)}
    assert pool.slot_bytes() == 0 and pool.state_slots_in_use == 0
    slot = pool.allocate(40)
    assert pool.slots_in_use == 1 and pool.state_slots_in_use == 0
    pool.reset_slot(slot)                   # nothing to zero: no program
    assert pool.slot_resets == 0
    # a pool with a slot array still reports its occupants
    other = HybridStatePool(2, 64, paged={"k": (1, 8, jnp.float32)},
                            slotted={"s": (1, (4,), jnp.float32)},
                            page_tokens=ROW)
    other.allocate(10)
    assert other.state_slots_in_use == 1


def test_reference_lists_leaves_by_layer_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/0/self_attn/indexer/wq/kernel"] == (64, 32)
    assert shapes["layers/0/self_attn/indexer/wk/kernel"] == (64, 8)
    assert shapes["layers/2/self_attn/indexer/weights_proj/kernel"] == (64, 4)
    assert shapes["layers/1/self_attn/q_norm/scale"] == (16,)
    assert shapes["layers/1/mlp/gate/kernel"] == (64, 16)      # published
    assert shapes["layers/1/mlp/experts/up_proj"] == (4, 64, 32)   # held
    assert not any("shared" in k or "bias" in k.split("/")[-1]
                   for k in shapes if "indexer/k_norm" not in k)
    D = ref.bind(CFG)
    assert (D["held"], D["experts"], D["topk"]) == ((4, 4), 16, 24)
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src.replace(
        "It imports nothing of the\nprogram", "")
    assert 'default_matmul_precision("highest")' in src


def test_background_loop_streams_tokens():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    got = []
    eng.start()
    try:
        fut = eng.submit(np.arange(50, dtype=np.int32) % 96,
                         max_new_tokens=12,
                         stream_cb=lambda rid, tok: got.append(tok))
        toks = fut.result(timeout=120)
    finally:
        eng.stop()
    assert got == toks and len(toks) == 12


# -- (f) the programs as lowered ---------------------------------------------
# (this family's two at the tiny shapes, off the TPU, are held with the other
# families' in ``test_mimo_v2.py::PARENT_TEXT``)

def test_the_cells_prefill_program_holds_no_scores_of_a_key_block(monkeypatch):
    """``_keye_prefill_chunk_jit`` lowered for a TPU at the cell's own
    shapes (16 rows of 128, 6 layers, 16 experts held, tables of 128 pages):
    a layer's walk is one Mosaic call, and no float32 array of the program
    is as large as a key block's scores were (16 rows x 32 heads x 128
    queries x 512 keys, 134 MB: ``PERF.md``, PR 42), none at all has a
    query axis, a key axis and the heads."""
    import json
    import re

    from benchmarks.models import keye_serve
    from deepspeed_tpu.ops import paged_prefill

    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    with open("benchmarks/configs/keye_vl2_30b_serve_ep8.json") as f:
        cfg = json.load(f)
    m = keye_serve.model_config(cfg)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    params = weights_mod.nest({k: sds(tuple(v))
                               for k, v in ref.weight_shapes(cfg).items()})
    R, L, pages, mp, i32 = 16, m.num_hidden_layers, 5121, 128, jnp.int32
    state = {"kv": sds((L, pages, 128, 8, 128)),
             "ik": sds((L, pages, 64, 128))}
    text = keye_family._keye_prefill_chunk_jit.trace(
        params, state, sds((R, 128), i32), sds((R,), i32), sds((R,), i32),
        sds((R,), i32), sds((R, mp), i32), cfg=m, page_tokens=128,
        keep_logits=False).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == L
    shapes = {tuple(int(d) for d in dims.split("x"))
              for dims in re.findall(r"tensor<([0-9x]+)xf32>", text)}
    # a key axis: a block of 512 keys, or the table's 16,384 positions,
    # behind the rows' axis
    keyed = {s for s in shapes if s[0] == R and {512, 128 * mp} & set(s[1:])}
    assert keyed and max(map(np.prod, keyed)) < 16 * 32 * 128 * 512, keyed
    assert not re.search(r"tensor<16x4x8x128x\d+xf32>|x4x8x128x512x", text)


def test_the_cells_decode_program_attends_the_tiles_as_they_lie(monkeypatch):
    """``_keye_decode_step_jit`` lowered for a TPU at the cell's own shapes
    (64 lanes, 6 layers, 16 experts held, tables of 128 pages, ``K``
    2,048): a layer's attention is one Mosaic call more than the program
    holds where the kernel is not taken, the selected tiles are still one
    gather a layer of ``(1, 1, 1, 8, 128)`` slices of ``kv``, and no
    product takes a half of them laid out again (``64 x 2048 x 4 x 128``,
    134 MB a layer and operand: ``PERF.md``, PR 43)."""
    import json
    import re

    from benchmarks.models import keye_serve
    from deepspeed_tpu.ops import paged_prefill

    with open("benchmarks/configs/keye_vl2_30b_serve_ep8.json") as f:
        cfg = json.load(f)
    m = keye_serve.model_config(cfg)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    params = weights_mod.nest({k: sds(tuple(v))
                               for k, v in ref.weight_shapes(cfg).items()})
    B, L, pages, mp, i32 = 64, m.num_hidden_layers, 5121, 128, jnp.int32
    state = {"kv": sds((L, pages, 128, 8, 128)),
             "ik": sds((L, pages, 64, 128))}

    def lowered():
        return keye_family._keye_decode_step_jit.trace(
            params, state, sds((B,), i32), sds((B,), i32), sds((B,), bool),
            sds((B, mp), i32), cfg=m, page_tokens=128,
            keep_logits=False).lower(lowering_platforms=("tpu",)).as_text()

    halves = r"tensor<64x2048x4x128xbf16>"
    plain = lowered()
    assert len(re.findall(r"stablehlo.dot_general.*" + halves, plain)) == 2 * L
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    keye_family._keye_decode_step_jit.clear_cache()  # traced for the CPU
    text = lowered()
    keye_family._keye_decode_step_jit.clear_cache()
    assert (text.count("tpu_custom_call")
            == plain.count("tpu_custom_call") + L)
    assert text.count("selected_tiles_attention") >= L
    gathers = re.findall(r"stablehlo.gather.*?slice_sizes = array<i64: "
                         r"([0-9, ]+)>.*?\(tensor<([0-9x]+)xbf16>", text)
    tiles = [g for g in gathers if g[1] == f"{L}x{pages}x128x8x128"]
    assert len(tiles) == L and {g[0] for g in tiles} == {"1, 1, 1, 8, 128"}
    assert not re.search(r"stablehlo.dot_general.*" + halves, text)
    assert halves not in text
