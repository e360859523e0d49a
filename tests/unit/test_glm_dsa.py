"""GLM-5.2 at a small size on the CPU: the program (``models/glm_dsa.py``,
the functions it shares with the other decoders, ``parallel/expert.py``'s
sigmoid router, ``ServingEngine`` through its family seam) against the plain
reference (``benchmarks/refs/glm_dsa_ref.py``), which follows the published
equations: expanded keys and values, rotation in place.

The tiny size keeps the shape of the cell's: published layers 2-7 of eight
(layer 2 dense and ``full``, layers 3-5 experts and ``shared``, layer 6
experts and ``full``, layer 7 experts and ``shared``); 4 heads of (12 nope |
4 rope) on a latent of 24 + 4 behind a query latent of 32; an indexer of 4
heads of 8 on one key head with 4 of the 8 channels rotated, ``index_topk``
24 in pages of 16 (so that a context of a few pages already prunes, as every
context of the cell does); 16 experts top-4 of which a share of 4 is held;
float32 parameters, so the program and the reference may differ by rounding
order only. With 4 indexer heads a sixteenth of the index scores are exactly
zero (every head's product negative), so ties AT the threshold are common
here: the order among equal scores is tested whether it is meant to be or
not."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import glm_dsa_ref as ref
from benchmarks.refs import kimi_linear_ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families import glm_dsa as glm_family
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import glm_dsa as gd
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod
from tests.unit import test_mimo_v2

KINDS = ["full", "full", "full", "shared", "shared", "shared", "full",
         "shared"]
MLPS = ["dense"] * 3 + ["sparse"] * 5
CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 12,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "qk_head_dim": 16, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 8, "index_topk": 24,
    "index_topk_freq": 4, "index_skip_topk_offset": 3,
    "index_topk_pattern": None, "indexer_types": KINDS,
    "mlp_layer_types": MLPS, "first_k_dense_replace": 3,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "rope_interleave": True, "indexer_rope_interleave": True,
    "attention_bias": False, "tie_word_embeddings": False,
    "max_position_embeddings": 4096, "num_nextn_predict_layers": 1,
    "share": {"n_routed_experts_published": 16, "experts_first": 4,
              "first_layer": 2},
}
ROW = 16                          # a page and a prefill row
TOPK = CFG["index_topk"]
PUBLISHED = 16                    # experts the router scores
LAYERS = range(2, 8)              # the published numbers of the layers held
FULL, SHARED = (2, 6), (3, 4, 5, 7)


def model_config(cfg=CFG, **over):
    share = cfg["share"]
    return gd.GlmDsaConfig.from_dict(
        dict(cfg, n_routed_experts=share["n_routed_experts_published"],
             **over),
        experts_held=(share["experts_first"], cfg["n_routed_experts"]),
        first_layer=share["first_layer"])


@functools.lru_cache(maxsize=None)
def _weights(held, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, n_routed_experts=held)), seed,
        jnp.float32)


def make(seed=7):
    flat = _weights(CFG["n_routed_experts"], seed)
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


def _serve(flat, params, mcfg, call_rows):
    eng = engine(params, mcfg, prefill_chunk_tokens=call_rows * ROW)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in LENGTHS]
    futs, seen, occupants = served_logits(eng, prompts, NEW)
    return flat, eng, prompts, futs, seen, occupants


@functools.lru_cache(maxsize=None)
def _served(call_rows):
    return _serve(*make(), call_rows)


# -- (a) prefill in rows, then decode through the pages ----------------------

@pytest.mark.parametrize("call_rows", [1, 4])
def test_engine_logits_match_the_reference_forward_pass(call_rows):
    """Prompts shorter than ``index_topk`` (5, 10, a page of 16: the
    selection must then be every position and attention dense latent
    attention), just over it (32 + decode), and several times it (67, 70,
    150: six times ``index_topk``, so that the selection prunes in prefill
    and in every decode step); chunk edges fall inside every prompt longer
    than a call; several prompts in one prefill call, several lanes at once,
    and more requests than lanes, so that lanes and pages get a second
    occupant that joins mid-run and must read nothing of the first though
    nothing is reset. Every decode step's logits are compared, lane by lane,
    with the reference's one forward pass over the prompt and the tokens
    served so far: the program attends absorbed over cached latent rows, the
    reference expanded over keys and values it makes for every position; the
    program keeps its rotated channels even ones first, the reference in
    place. Tolerance 2e-4 on logits of spread ~0.15: float32 rounding order
    reads under 2e-6 here; one key selected otherwise reads 1e-3 to 1e-1
    (the wrong-selection test below)."""
    flat, eng, prompts, futs, seen, occupants = _served(call_rows)
    assert isinstance(eng.family, glm_family.GlmDsaFamily)
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
    assert snap["moe_layer_steps"] == 5 * (snap["decode_steps"] - 1)
    assert 0 < snap["moe_experts_touched"] <= snap["moe_picks_here"]
    # a lane that holds p positions scores p + 1 keys in each of the 2
    # layers that select and attends topk of them at most in each of the 6,
    # in 4 of them under another layer's selection; a lane that retires was
    # given one step more
    context = [n + j + 1 for n in LENGTHS for j in range(NEW - 1)]
    scored = 2 * sum(context)
    attended = sum(min(c, TOPK) for c in context)
    assert scored <= snap["dsa_keys_scored"] <= scored + 2 * 8 * 256
    assert 6 * attended <= snap["dsa_keys_attended"] <= 6 * (
        attended + 8 * TOPK)
    assert 3 * snap["dsa_layers_shared_attended"] == 2 * snap[
        "dsa_keys_attended"]
    assert snap["page_waits"] == 0
    # pages only: no slot holds state, no slot array is reported
    assert (snap["state_slots_in_use"], snap["state_pool_bytes"]) == (0, 0)
    assert snap["latent_pool_bytes"] == eng.pool.paged_bytes() > 0


def _first_positions(scores, qpos, topk):
    """Not the indexer's choice: the first ``topk`` positions."""
    Ts = scores.shape[1]
    return ((jnp.arange(Ts)[None, :] < topk)
            & (jnp.arange(Ts)[None, :] <= qpos[:, None]))


def _ties_high_first(scores, qpos, topk):
    """The right scores and the wrong order among equal ones: the HIGHER
    position first (``lax.top_k`` over the mirrored row)."""
    Ts = scores.shape[1]
    causal = jnp.arange(Ts)[None, :] <= qpos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)[:, ::-1]
    _, idx = jax.lax.top_k(masked, min(topk, Ts))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], Ts - 1 - idx].set(True)
    return picked & causal


@pytest.mark.parametrize("wrong", [_first_positions, _ties_high_first])
def test_the_engine_against_a_reference_that_selects_otherwise_fails(wrong):
    """The tolerance above tells a wrong selection: a reference that attends
    the first ``index_topk`` positions, or breaks ties the other way round,
    is 1e-3 or more from what the engine served."""
    flat, eng, prompts, futs, seen, _ = _served(4)
    assert worst_gap(flat, futs, prompts, seen, NEW, selection=wrong) > 1e-3


# -- (b) a shared layer attends what its full layer chose --------------------

def _perturbed(flat, layer, name="indexer/wq_b/kernel"):
    key = f"layers/{layer}/self_attn/{name}"
    noise = 0.02 * jax.random.normal(jax.random.PRNGKey(3), flat[key].shape)
    return dict(flat, **{key: flat[key] + noise})


def _one_pass(flat, ids):
    """The prefill program's hidden state after each layer's attention, for
    one prompt of ``ids`` in rows of a page."""
    mcfg = model_config()
    params = weights_mod.nest(flat)
    n = -(-len(ids) // ROW)
    pool = HybridStatePool(
        1, 256, paged={k: (rows, what, jnp.float32)
                       for k, (rows, what) in mcfg.cache_arrays.items()},
        slotted={}, page_tokens=ROW, reset=())
    pool.allocate(len(ids))
    padded = np.zeros(n * ROW, np.int32)
    padded[:len(ids)] = ids
    lens = np.minimum(ROW, len(ids) - ROW * np.arange(n)).astype(np.int32)
    tables = np.repeat(pool.page_tables[:1], n, 0)
    seen = []
    real = gd.mla_prefill

    def spy(*a):
        out = real(*a)
        seen.append(out[0])
        return out

    gd.mla_prefill = spy
    try:
        gd.prefill_chunk(params, mcfg, pool.state,
                         jnp.asarray(padded.reshape(n, ROW)),
                         jnp.zeros(n, jnp.int32),
                         jnp.asarray(ROW * np.arange(n, dtype=np.int32)),
                         jnp.asarray(lens), jnp.asarray(tables),
                         page_tokens=ROW)
    finally:
        gd.mla_prefill = real
    return [np.asarray(y).reshape(n * ROW, -1)[:len(ids)] for y in seen]


def test_shared_layers_attend_under_the_selection_of_the_full_layer_below():
    """Perturb the indexer of layer 6 (``full``): nothing below it moves,
    layer 6's own attention moves and so does layer 7's (``shared``), which
    has no indexer and whose input moved with layer 6's output. Perturb the
    indexer's QUERIES of layer 2: the attention outputs of layers 3, 4 and 5
    move though nothing of their own weights or, at layer 3, anything but
    the selection changed; the reference moves alike (the same logits to
    2e-4), so what moved is the set attended and not an accident of the
    program."""
    flat, _, _ = make()
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 96, 100).astype(np.int32)      # four times topk
    base = _one_pass(flat, ids)
    assert len(base) == 6
    moved6 = _one_pass(_perturbed(flat, 6), ids)
    for n in range(4):                                   # layers 2-5
        np.testing.assert_array_equal(moved6[n], base[n])
    assert np.abs(moved6[4] - base[4]).max() > 1e-4      # layer 6
    assert np.abs(moved6[5] - base[5]).max() > 1e-4      # layer 7
    moved2 = _one_pass(_perturbed(flat, 2), ids)
    # before the threshold of 24 every position is attended: nothing moves
    np.testing.assert_array_equal(moved2[0][:TOPK], base[0][:TOPK])
    for n in (0, 1, 2, 3):                               # layers 2-5
        assert np.abs(moved2[n][TOPK:] - base[n][TOPK:]).max() > 1e-4, n
    # layer 3's input moved only through layer 2's attention; with layer
    # 2's OUTPUT put back, only the selection it handed on differs. Easier
    # to read off the reference: a shared layer given the unperturbed
    # selection equals the unperturbed pass
    want = reference_logits(_perturbed(flat, 2), ids)
    mcfg = model_config()
    eng = engine(weights_mod.nest(_perturbed(flat, 2)), mcfg)
    futs, seen, _ = served_logits(eng, [ids], 4)
    toks = futs[0].result(timeout=1)
    want = reference_logits(_perturbed(flat, 2), np.concatenate([ids, toks]))
    got = np.stack(seen[futs[0].request_id][:3])
    np.testing.assert_allclose(got, want[len(ids):len(ids) + 3], atol=2e-4)


def test_a_shared_layer_with_indexer_weights_is_refused_by_its_number():
    flat, params, mcfg = make()
    extra = {k.replace("layers/2/", "layers/3/"): v for k, v in flat.items()
             if k.startswith("layers/2/self_attn/indexer/")}
    with pytest.raises(ValueError, match=r"layers/3/self_attn/indexer.*"
                       r"'shared'.*has indexer weights"):
        engine(weights_mod.nest(dict(flat, **extra)), mcfg)
    missing = {k: v for k, v in flat.items()
               if not k.startswith("layers/6/self_attn/indexer/")}
    with pytest.raises(ValueError, match=r"layers/6/self_attn/indexer.*"
                       r"'full'.*has no indexer weights"):
        engine(weights_mod.nest(missing), mcfg)
    # a share that would begin on a shared layer has no selection to take
    with pytest.raises(ValueError, match="first_layer=3"):
        gd.GlmDsaConfig.from_dict(
            dict(CFG, n_routed_experts=16, num_hidden_layers=4),
            first_layer=3)


def _traced(fn, params, mcfg, *args):
    """Every equation of the jaxpr of program ``fn`` (sub-jaxprs too) as
    ``(primitive, its scope)``."""
    out = []

    def walk(jaxpr, outer=""):
        for eqn in jaxpr.eqns:
            scope = f"{outer}/{eqn.source_info.name_stack}"
            out.append((eqn.primitive.name, scope))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, scope)

    walk(jax.make_jaxpr(lambda p, *a: fn(p, mcfg, *a, page_tokens=ROW))(
        params, *args).jaxpr)
    return out


def test_the_programs_index_and_select_twice_not_six_times():
    """The two runs of ``shared`` layers (3-5 and 7) run no index and no
    top-k. Decode: two ``top_k`` in the whole program beside the five
    routers', both under ``dsa_select``; six gathers of latent rows under
    ``dsa_fetch``, four of them under ``dsa_carry``; the lowered text holds the
    same ``top_k``s. Prefill: two loops under ``dsa_index`` (a ``full`` layer's
    walk of its indexer keys), six walks of latent pages under ``mla_attend``,
    four of them under ``dsa_carry``, and no sort or ``top_k`` outside the
    expert layers."""
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    pool = eng.pool
    B, R, mp = 3, 4, pool.page_tables.shape[1]
    zeros = lambda *s: jnp.zeros(s, jnp.int32)            # noqa: E731
    decode = _traced(gd.decode_step, params, mcfg, pool.state, zeros(B),
                     zeros(B), jnp.ones(B, bool), zeros(B, mp))
    # (the five routers pick their experts with a top_k of their own)
    top = [scope for prim, scope in decode
           if prim == "top_k" and "moe_route" not in scope]
    assert len(top) == 2 and all("dsa_select" in s for s in top)
    fetch = [scope for prim, scope in decode
             if prim == "gather" and "dsa_fetch" in scope]
    assert len(fetch) == 6
    assert sum("dsa_carry" in s for s in fetch) == 4
    assert not [1 for prim, scope in decode
                if "dsa_carry" in scope and "dsa_index" in scope]
    text = glm_family._glm_decode_step_jit.lower(
        params, pool.state, zeros(B), zeros(B), jnp.ones(B, bool),
        zeros(B, mp), cfg=mcfg, page_tokens=ROW, keep_logits=False).as_text()
    assert text.count("chlo.top_k") == 2 + 5
    prefill = _traced(gd.prefill_chunk, params, mcfg, pool.state,
                      zeros(R, ROW), zeros(R), zeros(R), zeros(R),
                      zeros(R, mp))
    assert not [1 for prim, scope in prefill if prim in ("top_k", "sort")
                and "moe_" not in scope]
    loops = [scope for prim, scope in prefill if prim == "while"]
    assert sum(s.endswith("dsa_index") for s in loops) == 2
    walks = [s for s in loops if s.endswith("mla_attend")]
    assert len(walks) == 6 and sum("dsa_carry" in s for s in walks) == 4


# -- (c) the share adds up ---------------------------------------------------

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile", "scoring"))


def test_sixteen_shares_add_up_to_the_uncut_references_expert_layer():
    """Sixteen chips that hold 1 of the 16 published experts each: the
    routed parts of their expert layers, each through ``routed_moe_ffn``
    with its own ``held``, add up with the shared expert counted ONCE to the
    reference's layer with all 16 held (every share computes the shared
    expert alike; the deployment adds it once)."""
    flat = _weights(PUBLISHED, 11)
    D = dict(ref.dims_of(dict(CFG, n_routed_experts=PUBLISHED)),
             experts_first=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 64), jnp.float32)
    w = ref._sub(flat, "layers/4/mlp/")
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.expert_ffn(w, x, D, "f32"))
        shared = np.asarray(kimi_linear_ref._swiglu(
            x, w["shared_experts/gate_proj/kernel"],
            w["shared_experts/up_proj/kernel"],
            w["shared_experts/down_proj/kernel"], "f32"))
    params = weights_mod.nest(flat)["layers"]["4"]["mlp"]
    routed = np.zeros_like(whole)
    parts = []
    for first in range(16):
        share = dict(params, experts={
            k: v[first:first + 1] for k, v in params["experts"].items()})
        y, _ = moe_ffn(share, x, None, k=4, scaling=2.5, renormalize=True,
                       held=(first, 1), tile=8)
        parts.append(np.asarray(y) - shared)
        routed += parts[-1]
        # and the reference given the same share gives the same part
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(
                {**w, **{f"experts/{k}": v for k, v in
                         share["experts"].items()}}, x,
                dict(D, experts_first=first, experts_held=1), "f32")
        np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                   atol=5e-6)
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    assert sum(np.abs(p).max() > 1e-4 for p in parts) >= 12   # shares add


def test_eight_vocabulary_slices_tile_the_logits():
    """The head's rows cut in eight: each share's logits are its columns of
    the whole head's, so the slices tile it (``vocab_first`` names where a
    share's rows start; traffic ids, logits and sampling are over the
    slice)."""
    _, params, mcfg = make()
    h = jax.random.normal(jax.random.PRNGKey(8), (5, 64), jnp.float32)
    head = params["lm_head"]["kernel"]
    whole = np.asarray(pl.lm_head(h, params["norm"]["scale"],
                                  mcfg.rms_norm_eps, head))
    tiles = [np.asarray(pl.lm_head(h, params["norm"]["scale"],
                                   mcfg.rms_norm_eps,
                                   head[:, first:first + 12]))
             for first in range(0, 96, 12)]
    np.testing.assert_allclose(np.concatenate(tiles, -1), whole, atol=1e-6)
    assert dataclasses.replace(mcfg, vocab_first=12).vocab_first == 12


def test_attention_and_the_dense_layer_are_whole_on_every_share():
    """What is not routed is not cut: the same attention and dense-layer
    weights whatever ``experts_held`` says (the reference's shapes for two
    shares differ in the ``experts/`` leaves alone)."""
    a = ref.weight_shapes(dict(CFG, n_routed_experts=4))
    b = ref.weight_shapes(dict(CFG, n_routed_experts=1))
    differ = {k for k in a if a[k] != b[k]}
    assert differ and all("/mlp/experts/" in k for k in differ)
    ref.bind(CFG)


# -- (d) absorbed and expanded; the rotation's convention --------------------

@pytest.mark.parametrize("fetch_block", [512, 8])
def test_absorbed_and_expanded_latent_attention_agree(monkeypatch,
                                                      fetch_block):
    """``attend_fetched`` over cached latent rows with ``W_kvb`` absorbed
    into the query and applied to the context, against keys and values
    expanded from the same rows for every position, head by head; the
    selection's 40 positions fetched at once (no whole block of 512: one
    fetch) and in five blocks of 8 with the softmax carried across them."""
    monkeypatch.setattr(gd, "FETCH_BLOCK", fetch_block)
    _, params, mcfg = make()
    p = params["layers"]["3"]["self_attn"]
    rng = np.random.default_rng(2)
    B, K = 3, 40
    x = jnp.asarray(rng.normal(size=(B, 64)), jnp.float32)
    positions = jnp.asarray([39, 20, 5])
    rows = jnp.asarray(rng.normal(size=(B, K, mcfg.latent_row)), jnp.float32)
    rows = rows.at[..., mcfg.latent_width:].set(0.0)
    chosen = jnp.arange(K)[None, :] <= positions[:, None]
    _, q, _ = gd.mla_project(p, mcfg, x, positions)
    # the rows as pages hold them: lane b's position k in page 1 + b at
    # column k of row 1 of a pool of two rows
    pool = jnp.zeros((2, 4, K, mcfg.latent_row)).at[1, 1:].set(rows)
    page = jnp.broadcast_to(1 + jnp.arange(B)[:, None], (B, K))
    col = jnp.broadcast_to(jnp.arange(K)[None, :], (B, K))
    got = gd.mla_output(p, mcfg, gd.attend_fetched(
        mcfg, q, pool, 1, page, col, chosen), jnp.float32)
    # expanded: q as projected, k = [c W^K | kr], v = c W^V
    nh, dn, dr = 4, 12, 4
    cq = pl.rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"],
                     mcfg.rms_norm_eps)
    qh = (cq @ p["q_b_proj"]["kernel"]).reshape(B, nh, dn + dr)
    q_rope = gd.rope_pairs(mcfg.rope, qh[..., dn:], positions, dr)
    wkv = p["kv_b_proj"]["kernel"].reshape(24, nh, dn + 16)
    c, kr = rows[..., :24], rows[..., 24:28]
    k_nope = jnp.einsum("bkc,chd->bkhd", c, wkv[..., :dn])
    v = jnp.einsum("bkc,chd->bkhd", c, wkv[..., dn:])
    s = (jnp.einsum("bhd,bkhd->bhk", qh[..., :dn], k_nope)
         + jnp.einsum("bhd,bkd->bhk", q_rope, kr)) / 4.0
    a = jax.nn.softmax(jnp.where(chosen[:, None], s, -1e30), -1)
    ctx = jnp.einsum("bhk,bkhd->bhd", a, v).reshape(B, -1)
    want = ctx @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def _rotate_half(x, positions, theta, r):
    """The OTHER convention: the rotated channels' two halves turn
    together."""
    inv = theta ** (-2.0 * np.arange(r // 2) / r)
    ang = np.asarray(positions, np.float64)[:, None, None] * inv
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang), x[..., r:]],
                          -1)


def test_rotation_is_over_interleaved_pairs_and_not_rotate_half():
    """The reference turns channels ``(2i, 2i + 1)`` together and leaves
    them in place; the program turns the same pairs and lays the results
    even ones first; rotate-half over the same channels is another
    function. Products of a rotated query with a rotated key are the
    reference's, and are NOT rotate-half's: a program that swapped the
    convention fails here (and in the engine test above, by 1e-2)."""
    rng = np.random.default_rng(9)
    T, n, hd, r = 7, 3, 8, 4
    q = rng.normal(size=(T, n, hd)).astype(np.float32)
    k = rng.normal(size=(T, n, hd)).astype(np.float32)
    pos = np.array([0, 1, 5, 17, 100, 1000, 4000])
    want_q = np.asarray(ref.rope_interleaved(jnp.asarray(q), jnp.asarray(pos),
                                             8e6, r))
    want_k = np.asarray(ref.rope_interleaved(jnp.asarray(k), jnp.asarray(pos),
                                             8e6, r))
    # by hand: pair i of position p turns by p * 8e6^(-2i / r)
    ang = pos[:, None] * 8e6 ** (-2.0 * np.arange(r // 2) / r)
    for i in range(r // 2):
        c, s = np.cos(ang[:, i])[:, None], np.sin(ang[:, i])[:, None]
        np.testing.assert_allclose(
            want_q[..., 2 * i], q[..., 2 * i] * c - q[..., 2 * i + 1] * s,
            atol=1e-5)
        np.testing.assert_allclose(
            want_q[..., 2 * i + 1], q[..., 2 * i + 1] * c + q[..., 2 * i] * s,
            atol=1e-5)
    np.testing.assert_array_equal(want_q[..., r:], q[..., r:])
    spec = pl.RopeSpec(rope_theta=8e6)
    got_q = np.asarray(gd.rope_pairs(spec, jnp.asarray(q), jnp.asarray(pos),
                                     r))
    got_k = np.asarray(gd.rope_pairs(spec, jnp.asarray(k), jnp.asarray(pos),
                                     r))
    # the program's layout: even channels first, then odd ones, then the rest
    order = list(range(0, r, 2)) + list(range(1, r, 2)) + list(range(r, hd))
    np.testing.assert_allclose(got_q, want_q[..., order], atol=1e-5)
    dots = np.einsum("tnd,snd->nts", got_q, got_k)
    np.testing.assert_allclose(
        dots, np.einsum("tnd,snd->nts", want_q, want_k), atol=1e-4)
    half = np.einsum("tnd,snd->nts", _rotate_half(q, pos, 8e6, r),
                     _rotate_half(k, pos, 8e6, r))
    assert np.abs(dots - half).max() > 0.1
    # and the shared helper of the sibling families IS rotate-half
    sibling = np.asarray(pl.apply_rope(
        pl.RopeSpec(rope_theta=8e6, partial_rotary_factor=r / hd),
        jnp.asarray(q), jnp.asarray(pos)))
    np.testing.assert_allclose(sibling, _rotate_half(q, pos, 8e6, r),
                               atol=1e-5)


# -- (e) the exact top-k, two ways ------------------------------------------

def _scores(seed, rows, keys, ties):
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(rows, keys)).astype(np.float32)
    if ties:                        # a few values, many equal scores, zeros
        s = np.round(s * 2).astype(np.float32) / 2
    return s


@pytest.mark.parametrize("ties", [False, True])
def test_the_two_exact_top_ks_choose_one_set(ties):
    """The prefill's bisection (``kth_largest`` and the tie counts of
    ``row_selection``, read back block by block through ``selected``) and
    the decode step's ``lax.top_k`` choose the same positions for the same
    scores, the lower position first among equals, -0 and +0 among them
    (``lax.top_k`` alone would put -0 below +0: the decode step makes them
    one number first); and both are the reference's ``select``."""
    T, S, span, topk = ROW, 4 * ROW, 2 * ROW, 24
    s = _scores(3, T, S, ties)
    start = S - T                                  # the row's positions
    pos = start + np.arange(T)
    causal = np.arange(S)[None, :] <= pos[:, None]
    want = np.asarray(ref.select(jnp.asarray(s), jnp.asarray(pos), topk))
    assert (want.sum(-1) == topk).all()
    # decode: a lane a query
    at, chosen = pl.select_topk(jnp.where(jnp.asarray(s) == 0, 0.0,
                                          jnp.asarray(s)), jnp.asarray(pos),
                                topk)
    got = np.zeros((T, S), bool)
    got[np.arange(T)[:, None], np.asarray(at)] = np.asarray(chosen)
    np.testing.assert_array_equal(got, want)
    # prefill: one row of T queries
    u = pl.sortable(jnp.where(jnp.asarray(causal), jnp.asarray(s), -jnp.inf))
    sel = pl.row_selection(u[None], jnp.asarray(pos)[None], topk, span,
                           jnp.float32)
    blocks = [np.asarray(pl.selected(
        j, jax.lax.dynamic_slice_in_dim(sel[0][0], j * span, span, 0),
        sel[1][0], sel[2][0], sel[3][0], sel[4])) for j in range(S // span)]
    np.testing.assert_array_equal(np.concatenate(blocks, 0).T, want)
    if ties:
        # the tie rule decided something: some query's threshold is shared
        least = np.sort(np.where(causal, s, -np.inf), -1)[:, -topk]
        assert ((np.where(causal, s, -np.inf) == least[:, None]).sum(-1)
                > 1).any()


# -- (f) the family, the pool and what is refused ----------------------------

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
    assert "glm_dsa" in str(err.value)


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


@pytest.mark.parametrize("key, value, named", [
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_interleave", False, "rope_interleave"),
    ("indexer_rope_interleave", False, "indexer_rope_interleave"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e4},
     "rope_parameters"),
    ("index_topk_pattern", [1, 0], "index_topk_pattern"),
    ("index_topk", 0, "index_topk"),
    ("indexer_types", ["full"] * 7 + ["sparse"], "indexer_types"),
    ("indexer_types", ["full"] * 5, "indexer_types names 5 layers"),
    ("mlp_layer_types", ["dense"] * 7 + ["moe"], "mlp_layer_types"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("n_shared_experts", 2, "n_shared_experts"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("hidden_act", "gelu", "hidden_act"),
    ("qk_head_dim", 24, "qk_head_dim"),
])
def test_the_configuration_refuses_what_the_program_does_not_compute(
        key, value, named):
    with pytest.raises(ValueError, match=named):
        model_config(**{key: value})


def test_config_reads_the_published_keys():
    mcfg = model_config()
    assert (mcfg.index_n_heads, mcfg.index_head_dim, mcfg.index_topk) == (
        4, 8, 24)
    assert mcfg.rope_theta == 8000000 and mcfg.head_dim == 12
    assert (mcfg.n_routed_experts, mcfg.experts_held) == (16, (4, 4))
    assert mcfg.layers == tuple(LAYERS) and mcfg.first_layer == 2
    assert [l for l in mcfg.layers if mcfg.selects(l)] == list(FULL)
    assert [l for l in mcfg.layers if not mcfg.selects(l)] == list(SHARED)
    assert [mcfg.layer_is_moe(l) for l in mcfg.layers] == [False] + [True] * 5
    assert mcfg.n_moe_layers == 5 and mcfg.num_nextn_predict_layers == 1
    assert mcfg.indexer_index == {2: 0, 6: 1}
    assert mcfg.latent_index == {l: n for n, l in enumerate(LAYERS)}
    # a latent row of 24 + 4 values lies in one 128-lane tile; the
    # indexer's keys have a row for each layer that selects and no other
    assert (mcfg.latent_width, mcfg.latent_row) == (28, 128)
    assert mcfg.cache_arrays == {"latent": (6, (128,)), "ik": (2, 8)}
    hash(mcfg)                              # static under jit
    whole = gd.GlmDsaConfig()               # the published numbers
    assert (whole.index_topk, whole.n_routed_experts, whole.experts_held) == (
        2048, 256, (0, 256))
    assert (whole.latent_width, whole.latent_row) == (576, 640)
    # the two published numbers repeat the published list: 21 of 78 select
    full = [l for l in whole.layers if whole.selects(l)]
    assert full == [0, 1, 2] + list(range(6, 78, 4)) and len(full) == 21
    assert whole.cache_arrays == {"latent": (78, (640,)), "ik": (21, 128)}
    assert sum(whole.layer_is_moe(l) for l in whole.layers) == 75
    assert dataclasses.replace(whole, experts_held=(0, 16)).experts_held == (
        0, 16)
    with pytest.raises(ValueError, match="experts_held"):
        gd.GlmDsaConfig(experts_held=(250, 16))


def test_the_pool_holds_pages_only_and_an_array_only_some_layers_have():
    """Two paged arrays behind one page table, described from
    ``cache_arrays``: latent rows for all six layers (a token's shape,
    tokens first), the indexer's keys for the two layers that select (a
    width, tokens last); no slot array: nothing to reset, nothing to
    report."""
    _, params, mcfg = make()
    eng = engine(params, mcfg, kv_pool_tokens=30 * ROW)
    pool = eng.pool
    assert type(pool) is HybridStatePool
    assert (pool.paged_names, pool.slot_names, pool.reset_names) == (
        ("latent", "ik"), (), ())
    assert {n: a.shape for n, a in pool.state.items()} == {
        "latent": (6, 31, ROW, 128), "ik": (2, 31, 8, ROW)}
    assert pool.paged_bytes() == 4 * (6 * 31 * ROW * 128 + 2 * 31 * 8 * ROW)
    assert pool.slot_bytes() == 0 and pool.state_slots_in_use == 0
    slot = pool.allocate(40)
    assert pool.slots_in_use == 1 and pool.state_slots_in_use == 0
    pool.reset_slot(slot)                   # nothing to zero: no program
    assert pool.slot_resets == 0
    assert eng.metrics.snapshot()["latent_pool_bytes"] == pool.paged_bytes()


def test_reference_lists_leaves_by_layer_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/2/self_attn/indexer/wq_b/kernel"] == (32, 32)
    assert shapes["layers/6/self_attn/indexer/wk/kernel"] == (64, 8)
    assert shapes["layers/6/self_attn/indexer/weights_proj/kernel"] == (64, 4)
    assert not any(f"layers/{l}/self_attn/indexer" in k for k in shapes
                   for l in SHARED)
    assert not any(k.startswith(("layers/0/", "layers/1/", "layers/8/"))
                   for k in shapes)
    assert shapes["layers/3/self_attn/kv_a_proj_with_mqa/kernel"] == (64, 28)
    assert shapes["layers/3/self_attn/kv_b_proj/kernel"] == (24, 4 * 28)
    assert shapes["layers/2/mlp/up_proj/kernel"] == (64, 96)       # dense
    assert shapes["layers/3/mlp/gate/kernel"] == (64, 16)      # published
    assert shapes["layers/3/mlp/gate/e_score_correction_bias"] == (16,)
    assert shapes["layers/3/mlp/experts/up_proj"] == (4, 64, 32)   # held
    assert shapes["layers/7/mlp/shared_experts/down_proj/kernel"] == (32, 64)
    D = ref.bind(CFG)
    assert (D["experts_first"], D["experts_held"], D["experts_routed"],
            D["topk"]) == (4, 4, 16, 24)
    src = open(ref.__file__).read()
    assert "deepspeed_tpu" not in src
    assert 'default_matmul_precision("highest")' in src


def test_decode_asks_for_every_expert_and_prefill_does_not(monkeypatch):
    _, params, mcfg = make()
    asked = []
    real = expert_mod.routed_moe_ffn

    def spy(*a, **kw):
        asked.append(kw["every_expert"])
        return real(*a, **kw)

    monkeypatch.setattr(expert_mod, "routed_moe_ffn", spy)
    state = {"latent": jnp.zeros((6, 9, ROW, 128)),
             "ik": jnp.zeros((2, 9, 8, ROW))}
    i32 = jnp.int32
    gd.decode_step(params, mcfg, state, jnp.zeros(2, i32), jnp.zeros(2, i32),
                   jnp.ones(2, bool), jnp.ones((2, 4), i32), page_tokens=ROW)
    assert asked == [True] * 5              # the dense layer asks nothing
    del asked[:]
    gd.prefill_chunk(params, mcfg, state, jnp.zeros((2, ROW), i32),
                     jnp.zeros(2, i32), jnp.zeros(2, i32),
                     jnp.full(2, ROW, i32), jnp.ones((2, 4), i32),
                     page_tokens=ROW)
    assert asked == [False] * 5


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
