"""Ouro at a small size on the CPU: the program (``models/ouro.py``, the
shared attention functions of ``models/paged_layers.py``, ``ops/
column_write.py``'s traced row, ``ServingEngine`` through its family seam)
against the plain reference (``benchmarks/refs/ouro_ref.py``), which follows
the published equations: one full forward pass a request with no cache.

The tiny size keeps what is published: as many key-value heads as query
heads (4 of 16), the whole head rotated at theta 1e6, four norms a layer,
four passes of three layers, so that the 12 cache rows are not the 3 layers
of weights; float32 parameters, so the program and the reference may differ
by rounding order only."""

import dataclasses
import functools
import re
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import ouro_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families import ouro as ouro_family
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.inference.serving.metrics import ServingMetrics
from deepspeed_tpu.models import ouro as ou
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.ops import column_write

CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "use_sliding_window": False,
    "sliding_window": None, "max_window_layers": 3, "hidden_act": "silu",
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "total_ut_steps": 4, "early_exit_threshold": 1, "model_type": "ouro",
}
ROW = 16                          # a page and a prefill row
TOL = 2e-4                        # on logits of spread ~0.15 (float32)


def model_config(cfg=CFG, **over):
    return ou.OuroConfig.from_dict(dict(cfg, **over))


@functools.lru_cache(maxsize=None)
def _weights(layers, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, num_hidden_layers=layers)), seed,
        jnp.float32)


def make(seed=7, **over):
    """``(flat weights, a fresh nested tree, program's configuration)``: the
    family consumes the tree's per-layer leaves, the flat names keep them
    for the reference."""
    flat = _weights(CFG["num_hidden_layers"], seed)
    ref.bind(CFG)
    return flat, weights_mod.nest(flat), model_config(**over)


PAD_T = 224


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


def worst_gap(flat, futs, prompts, seen, new, want_of=reference_logits):
    worst = 0.0
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        want = want_of(flat, np.concatenate([p, toks]))
        for j, got in enumerate(seen[f.request_id][:new - 1]):
            worst = max(worst, float(np.abs(got - want[len(p) + j]).max()))
    return worst


# -- (a) prefill in rows, then decode through the pages ----------------------

@pytest.mark.parametrize("call_rows", [1, 4])
def test_engine_logits_match_the_reference_forward_pass(call_rows):
    """Prompts shorter than a row (10, 5), equal to it, many rows long
    (150), one token past a row and past a call; chunk edges fall inside
    every prompt longer than a call; several prompts in one prefill call,
    several lanes at once, and more requests than lanes, so that lanes get
    a second occupant that must read nothing of the first though nothing is
    reset. Every decode step's logits are compared, lane by lane, with the
    reference's one forward pass over the prompt and the tokens served so
    far: a pass that read another pass's cache row, in prefill or in decode,
    would not agree. Tolerance 2e-4 on logits of spread ~0.15: float32
    rounding order reads under 1e-5 here."""
    flat, params, mcfg = make()
    call = call_rows * ROW
    eng = engine(params, mcfg, prefill_chunk_tokens=call)
    assert isinstance(eng.family, ouro_family.OuroFamily)
    assert (eng.family.rows, eng.family.row_tokens) == (call_rows, ROW)
    # a row a (pass, layer), not a row a layer of weights; pages only
    assert {k: v.shape[0] for k, v in eng.pool.state.items()} == {
        "k": 12, "v": 12}
    assert eng.family.paged_attn_layers == mcfg.cache_rows == 12
    rng = np.random.default_rng(1)
    lengths = (10, ROW, 150, ROW + 1, call + 3, 5, 70, 2 * ROW)
    new = 24
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
    assert worst_gap(flat, futs, prompts, seen, new) < TOL
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == sum(lengths)
    assert snap["moe_layer_steps"] == 0 and snap["moe_picks_here"] == 0
    assert snap["loop_passes"] == 4 * snap["decode_steps"]
    assert snap["loop_layer_calls"] == 12 * (
        snap["decode_steps"] + snap["prefill_chunks"])
    assert (snap["loop_cache_rows"], snap["loop_cache_bytes_per_token"]) == (
        12, 12 * 2 * 64 * 4)
    assert snap["decode_context_tokens"] >= sum(
        (new - 1) * n for n in lengths)
    assert snap["decode_attn_blocks_walked"] > 0
    assert snap["page_waits"] == 0 and snap["state_slots_in_use"] == 0


def test_the_counters_count_what_a_hand_made_step_owes(monkeypatch):
    """Three lanes at positions 4, 15 and 300 of four passes over three
    layers: 319 positions attended, 4 passes, 12 layer calls, and the work
    list's blocks in each of the 12 cache rows, from the host's mirror of
    the positions alone."""
    from deepspeed_tpu.inference.serving.families import slot_state

    monkeypatch.setattr(slot_state.SlotStateFamily, "decode_step",
                        lambda self, guard: ((), (), 0, 0))
    fam = ouro_family.OuroFamily(model_config())
    fam.paged_attn_layers = 12
    metrics = ServingMetrics()
    fam.loop = SimpleNamespace(
        pool=SimpleNamespace(positions=np.array([4, 15, 77, 300]),
                             page_tokens=ROW, pages_in_use=7),
        metrics=metrics,
        lanes=SimpleNamespace(requests={0: None, 1: None, 3: None}))
    fam.decode_step(None)
    fam.count_prefill(None, None)
    snap = metrics.snapshot()
    assert snap["decode_context_tokens"] == 4 + 15 + 300
    assert snap["pool_pages_in_use_steps"] == 7
    assert (snap["loop_passes"], snap["loop_layer_calls"]) == (4, 24)
    # blocks of 512 keys: one a lane, in 12 rows
    assert snap["decode_attn_blocks_walked"] == 12 * 3


# -- (b) a part left out ------------------------------------------------------

def _without_norm(k):
    """``rms_norm`` that leaves out the ``k``-th norm a traced program
    applies (1-4 the layer's, in the order of the equations; 5 the final
    norm of a pass)."""
    calls, real = [], pl.rms_norm

    def rms_norm(x, scale, eps):
        calls.append(1)
        return x if len(calls) == k else real(x, scale, eps)
    return rms_norm


def _norm_after_the_last_pass_only(flat, ids):
    """The reference's own pieces put together wrongly: the stack four
    times with no norm between the passes and ``RMSNorm_f`` once at the
    end."""
    D = ref.dims_of(CFG)
    row = np.zeros(PAD_T, np.int32)
    row[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        stack = ref.stacked_layers(flat, D)
        h = jnp.asarray(flat["embed_tokens/embedding"])[row]
        for _ in range(D["passes"]):
            h = ref.run_stack(stack, h, D)
        h = ref._rms(h, flat["norm/scale"], D["eps"])
        return np.asarray(jnp.matmul(h, flat["lm_head/kernel"]))[:len(ids)]


@pytest.mark.parametrize("broken", [
    "three_passes", "one_row_a_layer", "final_norm_once", "no_output_norm",
    "no_norm_between_passes"])
def test_the_engine_against_the_reference_fails_when_a_part_is_left_out(
        broken, monkeypatch):
    """Acceptance: three passes for four, one cache row a layer shared by
    the passes (pass ``t`` then reads what pass ``t - 1`` wrote at the
    token's own position and what the LAST pass wrote at the earlier ones),
    ``RMSNorm_f`` only after the last pass, a missing output norm, no final
    norm between passes: each moves the served logits by far more than the
    tolerance of (a). The broken programs are traced under a configuration
    of their own (another ``max_position_embeddings``, read by no program),
    so that no sound trace is reused."""
    flat, params, mcfg = make(max_position_embeddings=4000 + len(broken))
    want_of = reference_logits
    if broken == "three_passes":
        mcfg = dataclasses.replace(mcfg, total_ut_steps=3)
    elif broken == "one_row_a_layer":
        monkeypatch.setattr(ou, "_cache_row", lambda cfg, t, l: l)
    elif broken == "final_norm_once":
        want_of = _norm_after_the_last_pass_only     # the reference's side
    elif broken == "no_output_norm":
        monkeypatch.setattr(pl, "rms_norm", _without_norm(2))
    else:
        monkeypatch.setattr(pl, "rms_norm", _without_norm(5))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (20, 50)]
    futs, seen, _ = served_logits(engine(params, mcfg), prompts, 6)
    assert worst_gap(flat, futs, prompts, seen, 6, want_of) > 10 * TOL


def test_the_sound_program_agrees_where_the_broken_ones_do_not():
    """The same prompts through the program as it is: inside the
    tolerance, under the same configuration trick."""
    flat, params, mcfg = make(max_position_embeddings=3999)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (20, 50)]
    futs, seen, _ = served_logits(engine(params, mcfg), prompts, 6)
    assert worst_gap(flat, futs, prompts, seen, 6) < TOL


# -- (c) the exit gate -------------------------------------------------------

def test_the_exit_distribution_sums_to_one_and_threshold_one_is_the_last():
    """``p`` over the passes from the gate on every pass's normed output:
    non-negative, sums to one; at the published threshold of 1 a token
    leaves at the last pass whatever the gate says (a sum that rounds below
    one falls back to it), at a threshold of 0 at the first."""
    flat, _, _ = make()
    D = ref.dims_of(CFG)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 96, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        hs = ref.pass_states(flat, ids, D)
    assert hs.shape == (4, 40, 64)
    g = ref.gate_logits(flat, hs)
    assert g.shape == (40, 4)
    for gates in (g, 50.0 * g, jnp.full((5, 4), -40.0), jnp.full((5, 4), 40.0),
                  jnp.asarray(np.random.default_rng(4).normal(size=(64, 4)),
                              jnp.float32) * 3):
        p = np.asarray(ref.exit_distribution(gates))
        assert (p >= 0).all()
        np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
        assert (np.asarray(ref.exit_pass(p, CFG["early_exit_threshold"]))
                == 3).all()
        assert (np.asarray(ref.exit_pass(p, 0.0)) == 0).all()
    # by hand: lambda = 1/2 at every pass
    np.testing.assert_allclose(
        np.asarray(ref.exit_distribution(jnp.zeros(4))),
        [0.5, 0.25, 0.125, 0.125])
    # a sure gate at the second pass: a threshold under one leaves there
    p = ref.exit_distribution(jnp.asarray([-40.0, 40.0, 0.0, 0.0]))
    assert int(ref.exit_pass(p, 0.9)) == 1 and int(ref.exit_pass(p, 1.0)) == 3


# -- (d) what the configuration refuses ---------------------------------------

@pytest.mark.parametrize("key, value", [
    ("early_exit_threshold", 0.9), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("total_ut_steps", 0),
    ("tie_word_embeddings", True)])
def test_the_configuration_refuses_by_name_what_it_does_not_compute(
        key, value):
    with pytest.raises(ValueError, match=key):
        model_config(**{key: value})


def test_the_configuration_reads_the_published_keys():
    published = ou.OuroConfig()
    assert (published.num_hidden_layers, published.total_ut_steps,
            published.cache_rows) == (48, 4, 192)
    assert published.cache_widths == {"k": 2048, "v": 2048}
    assert published.cache_values_per_token * 2 == 1572864       # bytes
    assert published.attention == pl.AttentionShape(16, 16, 128, 128)
    assert published.n_moe_layers == 0
    mcfg = model_config()
    assert (mcfg.cache_rows, mcfg.rope_theta, mcfg.early_exit_threshold) == (
        12, 1000000, 1)
    assert hash(mcfg) == hash(model_config())            # a static argument


def test_the_family_refuses_by_name_what_it_cannot_honour():
    _, params, mcfg = make()
    for bad, word in ((dict(prefix_cache_mb=1), "prefix_cache_mb"),
                      (dict(speculative_k=2), "speculative_k"),
                      (dict(kv_cache_dtype="bf16"), "kv_cache_dtype"),
                      (dict(prefill_chunk_tokens=24), "prefill_chunk_tokens"),
                      (dict(mesh_shape=(1, 2)), "mesh_shape")):
        with pytest.raises(UnsupportedOptionError, match=word):
            engine(params, mcfg, **bad)
    assert "layers" in params                    # a refusal consumes nothing


# -- (e) the walk is rolled --------------------------------------------------

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _lowered(layers, passes):
    """Both programs traced (not compiled) at ``layers`` x ``passes``."""
    cfg = dict(CFG, num_hidden_layers=layers, total_ut_steps=passes)
    mcfg = ou.OuroConfig.from_dict(cfg)
    flat = {k: _sds(v) for k, v in ref.weight_shapes(cfg).items()}
    ref.bind(CFG)
    params = jax.eval_shape(ou.stack_layers, weights_mod.nest(flat))
    slots, pages, mp, rows = 3, 9, 4, 4
    state = {n: _sds((mcfg.cache_rows, pages, 64, ROW)) for n in ("k", "v")}
    i32 = jnp.int32
    decode = ouro_family._ouro_decode_step_jit.lower(
        params, state, _sds((slots,), i32), _sds((slots,), i32),
        _sds((slots,), jnp.bool_), _sds((slots, mp), i32), cfg=mcfg,
        page_tokens=ROW, keep_logits=False)
    prefill = ouro_family._ouro_prefill_chunk_jit.lower(
        params, state, _sds((rows, ROW), i32), _sds((rows,), i32),
        _sds((rows,), i32), _sds((rows,), i32), _sds((rows, mp), i32),
        cfg=mcfg, page_tokens=ROW, keep_logits=False)
    return decode.as_text(), prefill.as_text()


def test_neither_programs_text_grows_with_the_passes_or_the_layers():
    """(2 layers, 2 passes) and (6 layers, 4 passes) lower to the same
    number of lines, program by program: one layer's text inside two loops,
    whatever the counts. (Unrolled, the second would be six times the
    first.)"""
    small, large = _lowered(2, 2), _lowered(6, 4)
    for a, b in zip(small, large):
        assert "stablehlo.while" in a
        assert len(a.splitlines()) == len(b.splitlines()) > 300
    # and the cache row is computed, not written out: no program's text
    # names a row past the first
    assert small[0] != large[0]                       # the shapes do differ


def test_the_pools_are_the_loops_carries():
    """Both pool arrays go through both loops of the decode program as
    carries and come out as its results: the loops' state holds the pool's
    type twice, and the program aliases its two pool arguments to its
    results (the family donates them)."""
    text = _lowered(2, 2)[0]
    pool = "tensor<4x9x64x16xf32>"
    whiles = [l for l in text.splitlines() if "stablehlo.while" in l]
    assert sum(l.count(pool) >= 2 for l in whiles) >= 2
    assert len(re.findall(r"tf.aliasing_output", text)) == 2


# -- (f) a traced row --------------------------------------------------------

@pytest.mark.parametrize("shape, index", [
    ((6, 9, 32, 16), ([3, 0, 7, 0],)),                      # pages
    ((3, 4, 2, 32, 16), ([0, 1, 2, 3], [1, 0, 1, 1]))])     # a ring's blocks
def test_write_columns_with_a_traced_row_equals_it_with_an_int(shape, index):
    """The plain branch and the Pallas kernel (interpret mode), each with
    the row as a traced scalar, against the plain branch with a Python
    int; a lane with column -1 writes nothing."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    index = [jnp.asarray(i, jnp.int32) for i in index]
    new = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    col = jnp.asarray([5, -1, 15, 2], jnp.int32)
    n = shape[0] - 2
    want = np.asarray(column_write.write_columns(pool, (n, *index), new, col))
    assert not np.array_equal(want, np.asarray(pool))
    for write in (column_write.write_columns, functools.partial(
            column_write._write_columns_pallas, interpret=True)):
        got = jax.jit(lambda pool, n, write=write: write(
            pool, (n, *index), new, col))(pool, jnp.int32(n))
        np.testing.assert_array_equal(np.asarray(got), want)
        # every other row is as it was
        np.testing.assert_array_equal(np.asarray(got)[:n],
                                      np.asarray(pool)[:n])


def test_a_python_int_row_lowers_as_it_did(monkeypatch):
    """With a Python int the kernel takes no scalar more than it took: the
    row stays in the index map (``tests/unit/test_mimo_v2.py::PARENT_TEXT``
    holds the ten sibling programs to their text); traced, it is one more
    scalar ahead of the grid."""
    pool, new = _sds((6, 9, 32, 16)), _sds((4, 32))
    phys, col = _sds((4,), jnp.int32), _sds((4,), jnp.int32)

    def operands(fn, *args):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        call = next(l for l in text.splitlines() if "tpu_custom_call" in l)
        return call.split("(", 1)[1].split(")")[0].count("%")

    by_int = operands(lambda pool, phys, new, col:
                      column_write._write_columns_pallas(
                          pool, (4, phys), new, col), pool, phys, new, col)
    traced = operands(lambda pool, n, phys, new, col:
                      column_write._write_columns_pallas(
                          pool, (n, phys), new, col),
                      pool, _sds((), jnp.int32), phys, new, col)
    assert (by_int, traced) == (4, 5)


# -- (g) stacking ------------------------------------------------------------

def test_stack_layers_consumes_the_per_layer_leaves():
    """The stacked tree holds every layer's leaf at its index; the tree it
    was made from has let the per-layer leaves go (the chip cannot hold the
    layers twice beside the pool)."""
    flat, params, _ = make()
    layers = params["layers"]
    out = ou.stack_layers(params)
    assert "layers" not in params and "layers" not in out
    assert layers == {}                           # every layer let go
    for l in range(3):
        np.testing.assert_array_equal(
            np.asarray(out["stack"]["mlp"]["down_proj"]["kernel"][l]),
            np.asarray(flat[f"layers/{l}/mlp/down_proj/kernel"]))
        np.testing.assert_array_equal(
            np.asarray(out["stack"]["post_attention_layernorm_2"]["scale"][l]),
            np.asarray(flat[f"layers/{l}/post_attention_layernorm_2/scale"]))
    assert out["lm_head"]["kernel"] is params["lm_head"]["kernel"]
    assert len(jax.tree_util.tree_leaves(out["stack"])) == 11
