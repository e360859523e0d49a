"""Laguna at a small size on the CPU: the program (``models/laguna.py``,
``parallel/expert.py``, ``ServingEngine`` through its family seam) against
the plain reference (``benchmarks/refs/laguna_ref.py``), which follows the
published equations.

Five layers (full + dense, three window + experts, full + experts); hidden
64, head size 16, 4 query heads in a full layer and 6 in a window layer on 2
key-value heads, a window of 32 and pages (rows) of 16, YaRN over half of a
full layer's head with an original length of 64, 8 experts top-2; float32
parameters, so the program and the reference may differ by rounding order
only. The prompts are longer than the window, than YaRN's original length
and than a prefill call."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import laguna_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families.laguna import LagunaFamily
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool
from deepspeed_tpu.models import laguna as lg
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod

FULL, WINDOW = "full_attention", "sliding_attention"
CFG = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False, "gating": True,
    "sliding_window": 32,
    "rope_parameters": {
        FULL: {"rope_theta": 10000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 64, "beta_slow": 1,
               "beta_fast": 8, "attention_factor": 1.1386,
               "partial_rotary_factor": 0.5},
        WINDOW: {"rope_type": "default", "rope_theta": 100,
                 "partial_rotary_factor": 1}},
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
}
ROW = 16                          # a page, which is a row of a prefill call
W = CFG["sliding_window"]

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile"))


@functools.lru_cache(maxsize=None)
def _weights(seed):
    """Made once a seed: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(ref.weight_shapes(CFG), seed,
                                    jnp.float32)


def make(seed=7):
    flat = _weights(seed)
    return flat, weights_mod.nest(flat), lg.LagunaConfig.from_dict(CFG)


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


# -- (a) prefill then decode through ServingEngine --------------------------

@pytest.mark.parametrize("call_rows", [2, 4])
def test_engine_logits_match_the_reference_forward_pass(call_rows):
    """Prompts shorter than the window (10), equal to a row, a window and a
    call, longer than the window by several rings (150: the ring wraps four
    times in prefill and again in decode), one token past a row, a ring and
    a call; several prompts in one prefill call, several lanes at once, and
    more requests than lanes, so that lanes get a second occupant that
    must read nothing of the first. A call of 2 rows is one window: a row's
    window then reaches back into the ring as earlier calls left it; a call
    of 4 rows is two, and only its last two rows may stay in the ring.
    Every decode step's logits are compared, lane by lane, with the
    reference's one forward pass over the prompt and the tokens served so
    far. Tolerance 2e-4 on logits of spread ~0.3: float32 rounding order
    reads under 3e-5 here, a bfloat16 anywhere on the way (a cached key, a
    router score) some 1e-3."""
    flat, params, mcfg = make()
    call = call_rows * ROW
    eng = engine(params, mcfg, prefill_chunk_tokens=call)
    assert isinstance(eng.family, LagunaFamily)
    assert (eng.family.rows, eng.family.row_tokens) == (call_rows, ROW)
    rng = np.random.default_rng(1)
    lengths = (10, ROW, 150, W + 1, ROW + 1, W, call, 5, call + 3, 70)
    new = 40                       # more than a ring: decode wraps it too
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in lengths]
    futs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    seen, occupants, calls = {}, {}, []
    eng.family.keep_logits = True
    real = eng.family.decode_step
    real_prefill = eng.family.prefill_program

    def spy(guard):
        lanes = {s: r.id for s, r in eng.lanes.requests.items()}
        out = real(guard)
        logits = np.asarray(eng.family.last_logits)
        for slot, rid in lanes.items():
            occupants.setdefault(slot, set()).add(rid)
            seen.setdefault(rid, []).append(logits[slot])
        return out

    def spy_prefill(params, state, ids, slots, starts, lens, tables, **kw):
        calls.append(tuple(np.asarray(a) for a in (slots, starts, lens)))
        return real_prefill(params, state, ids, slots, starts, lens, tables,
                            **kw)

    eng.family.decode_step = spy
    eng.family.prefill_program = spy_prefill
    assert eng.drain(max_steps=2000) < 2000
    assert max(len(v) for v in occupants.values()) >= 2   # a lane was reused
    several = [c for c in calls if len(set(c[0][c[2] > 0].tolist())) >= 2]
    assert several
    assert all((c[0][c[2] == 0] == 3).all() for c in calls)  # no slot
    assert all((c[1] % ROW == 0).all() for c in calls)
    worst = 0.0
    for f, p in zip(futs, prompts):
        toks = f.result(timeout=1)
        assert len(toks) == new
        want = reference_logits(flat, np.concatenate([p, toks]))
        # the first token comes from the prefill program
        assert toks[0] == int(want[len(p) - 1].argmax())
        assert new - 1 <= len(seen[f.request_id]) <= new
        assert toks[1:] == [int(want[len(p) + j].argmax())
                            for j in range(new - 1)]
        for j, got in enumerate(seen[f.request_id][:new - 1]):
            worst = max(worst, float(np.abs(got - want[len(p) + j]).max()))
    assert worst < 2e-4, worst
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == sum(lengths)
    # a call that read several prompts counts its time once
    assert 0 < snap["prefill_time_s"] <= snap["admit_time_s"] < (
        snap["loop_busy_s"] - snap["decode_time_s"])
    assert snap["prefill_chunks"] == len(calls)
    assert snap["prefill_positions_run"] == len(calls) * call
    assert eng.pool.slot_resets == 0          # a ring needs none
    assert snap["moe_layer_steps"] == 4 * (snap["decode_steps"] - 1)
    assert snap["tokens_emitted"] == (new - 1) * len(prompts)
    assert 0 < snap["moe_experts_touched"] <= snap["moe_picks_here"]
    # what the steps attended to: every emitted token read at least its
    # prompt, and the pages of at most three lanes of 256 were in use
    assert snap["decode_context_tokens"] >= sum(
        (new - 1) * n for n in lengths)
    assert 0 < snap["pool_pages_in_use_steps"] <= (
        snap["decode_steps"] * 3 * 256 // ROW)
    assert snap["page_waits"] == 0


# -- (b) a lane's second occupant reads nothing of the first ----------------

def test_a_shorter_request_after_a_longer_one_reads_nothing_of_it():
    """One lane, two requests in turn, the second shorter than the window
    and than the first: its ring still holds the first's keys at every
    slot it has not written (nothing is zeroed) and its pages were the
    first's. Its logits equal what it gets alone in a fresh engine, bit for
    bit: a position mask hides the rest of the ring, in the window layers,
    and the page table's own span the rest of the pages, in the full
    ones."""
    _, params, mcfg = make()
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 96, n).astype(np.int32) for n in (120, 9))

    def serve(eng, prompt):
        eng.family.keep_logits = True
        fut = eng.submit(prompt, max_new_tokens=6)
        logits = []
        for _ in range(200):
            if not eng.pending():
                break
            eng.step()
            if eng.family.last_logits is not None and eng.lanes.requests:
                logits.append(np.asarray(eng.family.last_logits)[0])
        return fut.result(timeout=1), logits

    eng = engine(params, mcfg, max_slots=1)
    ta, _ = serve(eng, a)
    ring_before = np.asarray(eng.pool.state["wk"])
    assert np.abs(ring_before[:, 0]).min(axis=-2).min() > 0   # ring is full
    tb, lb = serve(eng, b)
    assert eng.pool.allocations == 2 and eng.pool.slot_resets == 0
    # most of the ring still holds the first occupant's keys
    same = (np.asarray(eng.pool.state["wk"]) == ring_before).all(axis=-2)
    assert same[:, 0].sum() >= 3 * (W - 9 - 6)
    alone = engine(params, mcfg, max_slots=1)
    tc, lc = serve(alone, b)
    assert tb == tc and ta != tb
    assert len(lb) == len(lc)
    for got, want in zip(lb, lc):
        np.testing.assert_array_equal(got, want)


# -- (c) the two rotations ---------------------------------------------------

def _complex_rope(x, positions, inv, r, factor):
    """Rotate-half as complex numbers: dimension ``i`` and ``i + r / 2`` of
    a head are the real and imaginary part of one number, multiplied by
    ``factor e^{i p inv_i}``."""
    z = x[..., :r // 2] + 1j * x[..., r // 2:r]
    z = z * factor * np.exp(1j * positions[:, None, None] * inv)
    return np.concatenate([z.real, z.imag, x[..., r:]], axis=-1)


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_rotation_matches_complex_multiplication(kind):
    """At the published sizes (head 128), positions under and over the
    original length (4,096). Float32 angles at position 16,000 carry an
    error of ~1e-3 radians on the fastest frequency: 2e-3 on values of
    size 1."""
    spec = lg.LagunaConfig().rope_full if kind == FULL else (
        lg.LagunaConfig().rope_window)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(6, 3, 128)).astype(np.float32)
    positions = np.array([0, 1, 511, 4095, 4097, 16000])
    inv, r = pl.rope_inv_freq(spec, 128)
    assert r == (64 if kind == FULL else 128)
    want = _complex_rope(x.astype(np.float64), positions, inv, r,
                         spec.attention_factor)
    got = pl.apply_rope(spec, jnp.asarray(x), jnp.asarray(positions))
    np.testing.assert_allclose(got, want, atol=2e-3)
    np.testing.assert_array_equal(np.asarray(got)[:, :, r:], x[:, :, r:])
    # and the reference's own
    got_ref = ref.rope(jnp.asarray(x), jnp.asarray(positions),
                       dataclasses.asdict(spec), 128)
    np.testing.assert_allclose(got_ref, want, atol=2e-3)


def test_yarn_frequencies_match_numbers_worked_by_hand():
    """``r = 64``, base 500,000, factor 64, original length 4,096: ``d(64)
    = 64 ln(4096 / (128 pi)) / (2 ln 500000) = 5.66``, so ``low = 5``;
    ``d(1) = 64 ln(4096 / (2 pi)) / (2 ln 500000) = 15.80``, so ``high =
    16``. Frequencies 0-5 are extrapolated (as published), 16-31
    interpolated (a 64th), and frequency 10 is 5/11 of the way."""
    inv, r = pl.rope_inv_freq(lg.LagunaConfig().rope_full, 128)
    assert r == 64 and inv.shape == (32,)
    base = 500000.0
    extrap = lambda i: base ** (-2 * i / 64)             # noqa: E731
    np.testing.assert_allclose(inv[:6], [extrap(i) for i in range(6)],
                               rtol=1e-12)
    np.testing.assert_allclose(inv[16:], [extrap(i) / 64
                                          for i in range(16, 32)], rtol=1e-12)
    np.testing.assert_allclose(
        inv[10], extrap(10) * (6 / 11) + extrap(10) / 64 * (5 / 11),
        rtol=1e-12)
    np.testing.assert_allclose(inv[1], 0.6636, rtol=1e-3)
    np.testing.assert_allclose(inv[31], 4.709e-8, rtol=1e-3)
    want, _, factor = ref.inv_freq(
        {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
         "original_max_position_embeddings": 4096, "beta_slow": 1,
         "beta_fast": 64, "attention_factor": 1.4158883083359672,
         "partial_rotary_factor": 0.5}, 128)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert factor == 1.4158883083359672
    plain, r = pl.rope_inv_freq(lg.LagunaConfig().rope_window, 128)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-12)


# -- (d) the gate and the head counts ----------------------------------------

@pytest.mark.parametrize("layer", [0, 1])
def test_a_zeroed_gate_halves_every_heads_output(layer):
    """``sigmoid(0) = 1/2``: with ``W_g`` zeroed a layer gives ``W_o (a /
    2)``, half of what it gives ungated; and a full layer (4 heads) and a
    window layer (6) read their own counts."""
    _, params, mcfg = make()
    p = dict(params["layers"][str(layer)]["self_attn"])
    heads = mcfg.num_attention_heads_per_layer[layer]
    assert heads == (4, 6)[layer]
    assert p["q_proj"]["kernel"].shape == (64, heads * 16)
    assert p["g_proj"]["kernel"].shape == (64, heads)
    p["g_proj"] = {"kernel": jnp.zeros_like(p["g_proj"]["kernel"])}
    ungated = dataclasses.replace(mcfg, gating=False)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 64)),
                    jnp.float32)
    positions = jnp.asarray([5, 40, 0], jnp.int32)
    active = jnp.ones(3, bool)
    if mcfg.is_window(layer):
        rings = (jnp.asarray(np.random.default_rng(7).normal(
            size=(3, 3, W // ROW, 32, ROW)), jnp.float32),) * 2

        def run(cfg):
            return pl.window_decode(
                p, cfg.attention(layer), x, *rings, 0, positions, active,
                window=W, rotate=lg._rotate(cfg, layer),
                gate=lg._gate(p, cfg, layer, x))[0]
    else:
        pools = (jnp.asarray(np.random.default_rng(7).normal(
            size=(2, 10, 32, ROW)), jnp.float32),) * 2
        tables = jnp.asarray(1 + np.arange(9).reshape(3, 3), jnp.int32)

        def run(cfg):
            return pl.gqa_decode(
                p, cfg.attention(layer), x, *pools, 0, tables, positions,
                active, ROW, rotate=lg._rotate(cfg, layer),
                gate=lg._gate(p, cfg, layer, x))[0]

    np.testing.assert_allclose(run(mcfg), 0.5 * run(ungated), atol=1e-6,
                               rtol=1e-5)


def test_window_rows_in_one_call_match_the_reference_layer():
    """Seven rows in one window-layer call: a prompt of four rows (two
    windows, the last row partial) between a one-row prompt and an empty
    row, and the second call of a prompt whose first 48 tokens an earlier
    call left in the ring. Each prompt's outputs equal the reference's
    layer over the whole prompt (2e-5 on outputs of size ~0.05: float32
    rounding order), and each ring holds its prompt's last 32 positions,
    position ``p`` at ``p % 32`` (block ``p % 32 // 16``, column ``p % 16``)."""
    flat, params, mcfg = make()
    layer, n = 2, 1
    p = params["layers"][str(layer)]["self_attn"]
    w = {k[len("layers/2/self_attn/"):]: v for k, v in flat.items()
         if k.startswith("layers/2/self_attn/")}
    D = ref.dims_of(CFG)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(7, ROW, 64)), jnp.float32)
    earlier = jnp.asarray(rng.normal(size=(3, ROW, 64)), jnp.float32)
    slots = jnp.asarray([2, 0, 0, 0, 0, 3, 1], jnp.int32)
    starts = jnp.asarray([0, 0, 16, 32, 48, 0, 48], jnp.int32)
    lens = jnp.asarray([16, 16, 16, 16, 7, 0, 9], jnp.int32)
    rings = (jnp.asarray(rng.normal(size=(3, 3, W // ROW, 32, ROW)),
                         jnp.float32),) * 2
    how = dict(window=W, rotate=lg._rotate(mcfg, layer))
    shape = mcfg.attention(layer)
    # the earlier call of the prompt in slot 1: three full rows
    _, wk, wv = pl.window_prefill(
        p, shape, earlier, *rings, n, jnp.asarray([1, 1, 1], jnp.int32),
        jnp.asarray([0, 16, 32], jnp.int32),
        jnp.asarray([16, 16, 16], jnp.int32), **how,
        gate=lg._gate(p, mcfg, layer, earlier))
    y, wk2, wv2 = pl.window_prefill(p, shape, x, wk, wv, n, slots, starts,
                                    lens, **how,
                                    gate=lg._gate(p, mcfg, layer, x))
    layer_ref = jax.jit(lambda w, x: ref.attention(w, x, D, layer, "f32"))
    flat_rows = lambda a, rows: a[np.asarray(rows)].reshape(-1, 64)  # noqa: E731
    cases = (  # (slot, the whole prompt so far, of which this call read)
        (2, flat_rows(x, [0])[:16], 16),
        (0, flat_rows(x, [1, 2, 3, 4])[:55], 55),
        (1, jnp.concatenate([earlier.reshape(-1, 64), x[6, :9]]), 9))
    got_of = {2: y[0], 0: flat_rows(y, [1, 2, 3, 4]), 1: y[6]}
    for slot, whole, took in cases:
        total = len(whole)
        want = layer_ref(w, whole)
        np.testing.assert_allclose(got_of[slot][:took], want[total - took:],
                                   atol=2e-5, rtol=2e-4)
        # the ring: position q of the prompt's last 32 at q % 32
        k_all = pl.apply_rope(
            mcfg.rope_window,
            (whole @ p["k_proj"]["kernel"]).reshape(-1, 2, 16),
            jnp.arange(total)).reshape(-1, 32)
        for q in range(max(0, total - W), total):
            np.testing.assert_allclose(
                wk2[n, slot, q % W // ROW, :, q % ROW], k_all[q], atol=1e-5)
    # other layers' rings and the empty row's lane are untouched
    np.testing.assert_array_equal(wk2[0], rings[0][0])
    np.testing.assert_array_equal(wk2[2], rings[0][2])


# -- (d2) what one decode step writes ----------------------------------------

@functools.partial(jax.jit, static_argnames=("l",))
def reference_cache(flat, ids, l):
    """Layer ``l``'s keys (rotated) and values at every position of ``ids
    [T]`` as the reference computes them: its own ``hidden_states`` cut at
    ``l`` layers is the layer's input. ``(k [T, KV * hd], v [T, KV * hd])``."""
    D = ref.dims_of(CFG)
    h = ref.hidden_states(flat, ids, dict(D, layers=l))
    x = ref._rms(h, flat[f"layers/{l}/input_layernorm/scale"], D["eps"])
    spec = dict(D["rope_window"] if D["window_layer"][l] else D["rope_full"])
    k = ref.rope((x @ flat[f"layers/{l}/self_attn/k_proj/kernel"]).reshape(
        len(ids), D["kv_heads"], D["head"]), jnp.arange(len(ids)), spec,
        D["head"])
    return (k.reshape(len(ids), -1),
            x @ flat[f"layers/{l}/self_attn/v_proj/kernel"])


def check_what_one_decode_step_writes(model, params, mcfg, widths, page,
                                      cache_of):
    """Four lanes over a state of random numbers: lane 0 has read 5 tokens,
    lane 1 ``W + 3`` (its next position wraps the ring), lanes 2 and 3 are
    inactive and both fall on the spare page 0. After one ``decode_step``
    of ``model`` (``models/laguna.py`` or ``models/mimo_v2.py``) the state
    differs from the state before in exactly one column a layer of each
    active lane's ring (column ``p % T`` of block ``p % W // T``) and of its
    page ``p // T``, which hold what ``cache_of(ids, layer) -> (k, v)`` says
    the reference computes at ``p``; an inactive lane's ring, every other
    block and every other page but the spare one are bit for bit what they
    were. ``widths`` names what a token caches in each of the pool's four
    arrays, ``page`` the tokens of a page and of a ring's block."""
    W, T = mcfg.sliding_window, page
    back, mp, lanes = W // T, 2 * W // T, 4
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, W + 3)]
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    nf, nw = len(mcfg.full_index), len(mcfg.window_index)
    shapes = {"k": (nf, 1 + lanes * mp, widths["k"], T),
              "v": (nf, 1 + lanes * mp, widths["v"], T),
              "wk": (nw, lanes, back, widths["wk"], T),
              "wv": (nw, lanes, back, widths["wv"], T)}
    state = {name: jnp.asarray(rng.normal(size=shape), jnp.float32)
             for name, shape in shapes.items()}
    rows = [(b, start, min(T, len(ids) - start))
            for b, ids in enumerate(prompts) for start in range(0, len(ids), T)]
    ids = np.zeros((len(rows), T), np.int32)
    for r, (b, start, n) in enumerate(rows):
        ids[r, :n] = prompts[b][start:start + n]
    slots, starts, lens = (jnp.asarray(c, jnp.int32) for c in zip(*rows))
    state, first, _ = jax.jit(
        lambda *a: model.prefill_chunk(params, mcfg, *a, page_tokens=T))(
            state, jnp.asarray(ids), slots, starts, lens,
            jnp.asarray(tables)[slots])
    ends = np.cumsum([-(-len(ids) // T) for ids in prompts]) - 1
    tokens = np.asarray([first[ends[0]], first[ends[1]], 7, 9], np.int32)
    positions = np.asarray([5, W + 3, 2, 2], np.int32)
    active = np.asarray([True, True, False, False])
    before = {name: np.asarray(x) for name, x in state.items()}
    after, *_ = jax.jit(
        lambda *a: model.decode_step(params, mcfg, *a, page_tokens=T))(
            state, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(active), jnp.asarray(tables))
    after = {name: np.asarray(x) for name, x in after.items()}
    written = {name: np.zeros(x.shape, bool) for name, x in before.items()}
    for l in range(mcfg.num_hidden_layers):
        window = mcfg.is_window(l)
        names = ("wk", "wv") if window else ("k", "v")
        n = (mcfg.window_index if window else mcfg.full_index)[l]
        for b in (0, 1):
            p = int(positions[b])
            whole = jnp.asarray(np.append(prompts[b], tokens[b]))
            at = ((n, b, p % W // T) if window
                  else (n, tables[b, p // T]))
            for name, want in zip(names, cache_of(whole, l)):
                np.testing.assert_allclose(after[name][at][:, p % T],
                                           want[p], atol=1e-5)
                written[name][at][:, p % T] = True
                if window and p >= W:       # the oldest slot, overwritten
                    np.testing.assert_allclose(before[name][at][:, p % T],
                                               want[p - W], atol=1e-5)
    for name in before:
        changed = after[name] != before[name]
        if name in ("k", "v"):
            changed[:, 0] = False           # the spare page: anyone's
        assert not (changed & ~written[name]).any(), name
        assert (changed | ~written[name]).all(), name


@pytest.mark.parametrize("page", [32, 16, 8])
def test_a_decode_step_writes_one_column_a_lane_and_nothing_else(page):
    """A ring of one block (pages of 32), of two and of four."""
    flat, params, mcfg = make()
    check_what_one_decode_step_writes(
        lg, params, mcfg, dict.fromkeys(("k", "v", "wk", "wv"), 32), page,
        lambda ids, l: reference_cache(flat, ids, l))


# -- (e) the expert layer, whole and in shares -------------------------------

def test_all_experts_held_equals_the_references_whole_layer():
    """8 of 8 held (the cell holds 256 of 256): no share, every pick lands
    here; and the two halves, with the shared expert counted once, add up
    to the same."""
    flat, params, mcfg = make()
    mlp = params["layers"]["2"]["mlp"]
    assert "e_score_correction_bias" not in mlp["gate"]
    m = {k[len("layers/2/mlp/"):]: v for k, v in flat.items()
         if k.startswith("layers/2/mlp/")}
    D = ref.dims_of(CFG)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    want = jax.jit(lambda m, x: ref.expert_ffn(m, x, D, "f32"))(m, x)
    y, stats = moe_ffn(mlp, x, k=2, scaling=2.5, renormalize=True,
                       held=(0, 8), tile=8)
    assert int(stats[0]) == 50 * 2            # every pick fell here
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=2e-5)
    shared_only = ref._swiglu(
        x, *(m[f"shared_experts/{n}/kernel"]
             for n in ("gate_proj", "up_proj", "down_proj")), "f32")
    total, picks = jnp.zeros_like(x), 0
    for first in (0, 4):
        part = dict(mlp, experts={k: v[first:first + 4]
                                  for k, v in mlp["experts"].items()})
        y_part, stats = moe_ffn(part, x, k=2, scaling=2.5, renormalize=True,
                                held=(first, 4), tile=8)
        total = total + y_part
        picks += int(stats[0])
        want_part = jax.jit(lambda m, x, first=first: ref.expert_ffn(
            m, x, D, "f32", held=(first, 4)))(m, x)
        np.testing.assert_allclose(y_part, want_part, atol=2e-6, rtol=2e-5)
    assert picks == 50 * 2
    np.testing.assert_allclose(total - shared_only, want, atol=5e-6,
                               rtol=5e-5)


# -- (f) admission under a page budget ---------------------------------------

def test_a_request_that_finds_no_pages_waits_at_the_head_of_the_queue():
    """Four lanes and a budget of 256 tokens (one full lane, 16 pages): a
    request of 10 pages and one of 5 are admitted, the third (4 pages)
    finds one page and waits with a slot free; the fourth, which would
    fit, waits behind it: order is kept. Nothing is dropped, every request
    gets its tokens, and the passes that ended for want of pages are
    counted."""
    _, params, mcfg = make()
    eng = engine(params, mcfg, max_slots=4, kv_pool_tokens=256)
    assert eng.pool.n_data_pages == 16
    rng = np.random.default_rng(9)
    sizes = [(150, 10), (70, 10), (50, 14), (5, 3)]     # pages: 10, 5, 4, 1
    futs = [eng.submit(rng.integers(0, 96, n).astype(np.int32),
                       max_new_tokens=m) for n, m in sizes]
    assert eng.step()["admitted"] == 2
    assert eng.pool.free_slots == 2 and eng.pool.free_pages == 1
    assert eng.scheduler.queue_depth() == 2
    assert eng.metrics.snapshot()["page_waits"] == 1
    eng.step()
    assert eng.metrics.snapshot()["page_waits"] == 2
    admitted = []
    real = eng.family.admit

    def spy(stats):
        before = [st.req.id for st in eng.family._prefilling]
        real(stats)
        admitted.extend(st.req.id for st in eng.family._prefilling
                        if st.req.id not in before)

    eng.family.admit = spy
    assert eng.drain(max_steps=500) < 500
    assert admitted == [futs[2].request_id, futs[3].request_id]
    assert [len(f.result(timeout=1)) for f in futs] == [m for _, m in sizes]
    assert eng.pool.free_pages == 16 and eng.pool.free_slots == 4
    assert eng.metrics.snapshot()["page_waits"] >= 2


# -- the counters of the decode attention's work list ---------------------

@pytest.mark.parametrize("family, layers", [("laguna", 2), ("nemotron_h", 1)])
def test_the_attention_block_counters_count_what_a_hand_made_schedule_owes(
        family, layers, monkeypatch):
    """Two decode steps of three lanes of known lengths (slots 0, 1 and 3;
    slot 2 is free and what its position reads is nobody's), through the
    family's own ``decode_step`` with the program's dispatch taken out: the
    pairs walked are each lane's blocks of 512 keys in each paged attention
    layer, the rectangle is every lane to the longest one's end."""
    from types import SimpleNamespace

    from deepspeed_tpu.inference.serving.families import slot_state
    from deepspeed_tpu.inference.serving.families.nemotron_h import (
        NemotronHFamily,
    )
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    monkeypatch.setattr(slot_state.SlotStateFamily, "decode_step",
                        lambda self, guard: ((), (), 0, 0))
    if family == "laguna":
        fam = LagunaFamily(lg.LagunaConfig.from_dict(CFG))
        fam.ring_layers = 3
    else:
        fam = NemotronHFamily(None)
    fam.paged_attn_layers = layers
    metrics = ServingMetrics()
    pool = SimpleNamespace(positions=np.array([5, 511, 9999, 1100]),
                           page_tokens=ROW, pages_in_use=0)
    fam.loop = SimpleNamespace(
        pool=pool, metrics=metrics,
        lanes=SimpleNamespace(requests={0: None, 1: None, 3: None}))
    before = metrics.snapshot()
    assert (before["decode_attn_blocks_walked"],
            before["decode_attn_blocks_dense"]) == (0, 0)
    fam.decode_step(None)                 # blocks 1, 1, 3
    pool.positions[[0, 1, 3]] += 1        # 511 -> 512: a second block
    fam.decode_step(None)                 # blocks 1, 2, 3
    snap = metrics.snapshot()
    assert snap["decode_attn_blocks_walked"] == layers * (5 + 6)
    assert snap["decode_attn_blocks_dense"] == layers * (3 * 3 + 3 * 3)
    # by page (a block is 512 / ROW pages): those a lane holds a key in,
    # which the kernel fetches, within those of the blocks walked
    per_block = 512 // ROW
    held = [np.array([5, 511, 1100]), np.array([6, 512, 1101])]
    assert snap["decode_attn_pages_fetched"] == layers * sum(
        int((p // ROW + 1).sum()) for p in held)
    assert snap["decode_attn_pages_in_blocks"] == layers * (5 + 6) * per_block
    assert (snap["decode_attn_pages_fetched"]
            < snap["decode_attn_pages_in_blocks"])
    # what the rings hold behind their masks: min(position + 1, 32) a lane
    # in each of Laguna's three window layers, and nothing for Nemotron-H
    assert snap["decode_ring_positions"] == (
        3 * ((6 + 32 + 32) + (7 + 32 + 32)) if family == "laguna" else 0)
    fam.loop.lanes.requests = {}          # a step with no lane owes nothing
    fam.decode_step(None)
    assert metrics.snapshot()["decode_attn_blocks_dense"] == layers * 18


@pytest.mark.parametrize("positions, equal", [
    ([5, 511, 1100], False),        # one page of four, a full block, 9 of 12
    ([511, 1023, 2047], True),      # every lane ends on a block's edge
    ([0], False), ([127], False), ([128], False),
])
def test_the_attention_page_counters_are_the_live_pages_of_the_walked_blocks(
        positions, equal):
    """``decode_attn_pages_fetched`` (what ``ops/paged_decode.py`` fetches)
    is at most ``decode_attn_pages_in_blocks`` (what the plain walk
    gathers), and equal only where every lane's last page is its block's
    last."""
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics
    from deepspeed_tpu.models.paged_layers import decode_key_span

    page, layers = 128, 3
    span = decode_key_span(page)
    held = np.array(positions)
    metrics = ServingMetrics()
    metrics.record_attn_blocks(held // span + 1, layers, held // page + 1,
                               span // page)
    snap = metrics.snapshot()
    fetched, gathered = (snap["decode_attn_pages_fetched"],
                         snap["decode_attn_pages_in_blocks"])
    assert fetched == layers * sum(p // page + 1 for p in positions)
    assert gathered == snap["decode_attn_blocks_walked"] * (span // page)
    assert (fetched == gathered) if equal else (fetched < gathered)
    metrics.record_attn_blocks(held[:0], layers, held[:0], span // page)
    assert metrics.snapshot()["decode_attn_pages_fetched"] == fetched


# -- (g) each unsupported option raises, by name -----------------------------

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
    "partition_rules": dict(partition_rules=((".*", (None,)),)),
    "prefill_chunk_tokens=0": dict(prefill_chunk_tokens=0),
    "prefill_chunk_tokens=40": dict(prefill_chunk_tokens=40),
    "kv_page_tokens=64": dict(kv_page_tokens=64, prefill_chunk_tokens=64),
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
    assert "laguna" in str(err.value)
    assert "recurrent" not in str(err.value)     # a ring is not


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4),
                 lambda: eng.handoff_install(0, {}, []),
                 lambda: eng.resume_handoff(0, [1], 2, 3)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


# -- the state's description and the configuration ---------------------------

def test_the_pool_holds_pages_and_rings_by_description():
    _, params, mcfg = make()
    eng = engine(params, mcfg, kv_pool_tokens=512)
    assert type(eng.pool) is HybridStatePool
    st = eng.pool.state
    # two full layers' pages: the key-value heads side by side, tokens last
    assert st["k"].shape == st["v"].shape == (2, 512 // ROW + 1, 2 * 16, ROW)
    # three window layers' rings, a lane each, in blocks laid out as pages
    assert st["wk"].shape == st["wv"].shape == (3, 3, W // ROW, 2 * 16, ROW)
    assert eng.pool.paged_names == ("k", "v")
    assert eng.pool.slot_names == ("wk", "wv") and eng.pool.reset_names == ()
    assert eng.pool.pool_tokens == 512 < 3 * 256


@pytest.mark.parametrize("reset, zeroed", [(None, ("a", "b")), ((), ()),
                                           (("b",), ("b",))])
def test_reset_slot_zeroes_what_the_family_says_needs_it(reset, zeroed):
    """The default is every slot array, as the families with recurrent
    state rely on (their own tests count the resets of their admissions);
    a ring behind a position mask is left out and costs no program."""
    pool = HybridStatePool(2, 32, paged={}, slotted={
        "a": (1, (4,), jnp.float32), "b": (2, (3, 2), jnp.float32)},
        page_tokens=16, reset=reset)
    pool.state = {n: jnp.ones_like(a) for n, a in pool.state.items()}
    slot = pool.allocate(8)
    pool.reset_slot(slot)
    for name in ("a", "b"):
        want = 0.0 if name in zeroed else 1.0
        assert float(pool.state[name][:, slot].max()) == want
        assert float(pool.state[name][:, 1 - slot].min()) == 1.0
    assert pool.slot_resets == (1 if zeroed else 0)
    with pytest.raises(ValueError, match="names no slot array"):
        HybridStatePool(2, 32, paged={}, slotted={}, reset=("c",))


def test_config_reads_the_published_keys_up_to_the_depth():
    mcfg = lg.LagunaConfig.from_dict(CFG)
    assert mcfg.num_hidden_layers == 5 and len(mcfg.layer_types) == 8
    assert [mcfg.is_window(l) for l in range(5)] == [False, True, True, True,
                                                     False]
    assert [mcfg.is_moe(l) for l in range(5)] == [False] + [True] * 4
    assert mcfg.full_index == {0: 0, 4: 1}
    assert mcfg.window_index == {1: 0, 2: 1, 3: 2}
    assert mcfg.n_moe_layers == 4 and mcfg.num_experts == 8
    assert mcfg.attention(1).num_attention_heads == 6
    assert mcfg.rope(0).rope_type == "yarn" and mcfg.rope(0).factor == 4
    assert mcfg.rope(1).rope_theta == 100
    hash(mcfg)                                # a static argument of the jit
    with pytest.raises(ValueError, match="names 3 layers"):
        dataclasses.replace(mcfg, layer_types=(FULL, WINDOW, WINDOW))
    with pytest.raises(ValueError, match="key-value"):
        dataclasses.replace(mcfg,
                            num_attention_heads_per_layer=(4, 5, 6, 6, 4))
    with pytest.raises(ValueError, match="router_weight_on_input"):
        dataclasses.replace(mcfg, moe_apply_router_weight_on_input=True)
    full = lg.LagunaConfig()
    assert full.num_hidden_layers == 40 and full.kv_width == 1024
    assert (len(full.full_index), len(full.window_index),
            full.n_moe_layers) == (10, 30, 39)
    assert full.rope_full.original_max_position_embeddings == 4096


def test_reference_lists_leaves_by_layer_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/0/self_attn/q_proj/kernel"] == (64, 4 * 16)
    assert shapes["layers/1/self_attn/q_proj/kernel"] == (64, 6 * 16)
    assert shapes["layers/1/self_attn/o_proj/kernel"] == (6 * 16, 64)
    assert shapes["layers/1/self_attn/g_proj/kernel"] == (64, 6)
    assert shapes["layers/1/self_attn/k_proj/kernel"] == (64, 2 * 16)
    assert shapes["layers/0/mlp/up_proj/kernel"] == (64, 96)
    assert shapes["layers/1/mlp/experts/gate_proj"] == (8, 64, 32)
    assert shapes["layers/1/mlp/experts/down_proj"] == (8, 32, 64)
    assert shapes["layers/1/mlp/gate/kernel"] == (64, 8)
    assert "layers/1/mlp/gate/e_score_correction_bias" not in shapes
    assert shapes["layers/1/mlp/shared_experts/up_proj/kernel"] == (64, 32)
    assert shapes["lm_head/kernel"] == (64, 96)
    assert not any(k.startswith("layers/5/") for k in shapes)
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
