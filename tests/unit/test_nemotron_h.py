"""Nemotron-H at a small size on the CPU: the program (``models/
nemotron_h.py``, ``parallel/expert.py``, ``ServingEngine`` through its family
seam) against the plain reference (``benchmarks/refs/nemotron_h_ref.py``),
which follows the published equations token by token.

Pattern ``MEMEM*EME``; hidden 64, 4 Mamba-2 heads of 16 with state 16 in 2
groups, rows of 16 tokens, 4 query heads on 2 key-value heads, 8 experts
top-2 of which 4 are held; float32 parameters, so the program and the
reference may differ by rounding order only."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.refs import nemotron_h_ref as ref
from benchmarks.refs import weights as weights_mod
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving.families.nemotron_h import (
    NemotronHFamily,
)
from deepspeed_tpu.inference.serving.families.slot_state import (
    PREFILL_HOLD_STEPS,
)
from deepspeed_tpu.inference.serving.family import UnsupportedOptionError
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.parallel import expert as expert_mod

CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 9,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*",
    "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
    "share": {"n_routed_experts_published": 8, "experts_first": 0},
}
ROW = CFG["chunk_size"]
CALL = 4 * ROW                    # positions a prefill call runs: 4 rows

moe_ffn = jax.jit(expert_mod.routed_moe_ffn, static_argnames=(
    "k", "scaling", "renormalize", "held", "tile"))


def model_config(cfg):
    share = cfg["share"]
    return nh.NemotronHConfig.from_dict(
        dict(cfg, n_routed_experts=share["n_routed_experts_published"]),
        experts_held=(share["experts_first"], cfg["n_routed_experts"]))


@functools.lru_cache(maxsize=None)
def _weights(held, seed):
    """Made once a size: every call of ``make_weights`` compiles anew."""
    return weights_mod.make_weights(
        ref.weight_shapes(dict(CFG, n_routed_experts=held)), seed,
        jnp.float32)


def make(cfg=CFG, seed=7, slow_decay=False):
    flat = _weights(cfg["n_routed_experts"], seed)
    if slow_decay:
        # A_log = -4 and dt_bias = -1: exp(dt A) is about 0.994 a token, so
        # the state keeps half of itself for some 120 tokens and a state
        # carried wrongly across a row, a call or into decode shows
        flat = {k: (jnp.full_like(v, -4.0) if k.endswith("A_log") else
                    jnp.full_like(v, -1.0) if k.endswith("dt_bias") else v)
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
              kv_page_tokens=16, prefill_chunk_tokens=CALL)
    kw.update(over)
    return ServingEngine(params, mcfg, ServingConfig(**kw))


# -- (a) prefill then decode through ServingEngine --------------------------

@pytest.mark.parametrize("slow_decay", [False, True])
def test_engine_logits_match_the_reference_forward_pass(slow_decay):
    """Prompts shorter than, equal to and longer than a row and a call,
    several prompts in one prefill call with empty rows among them, several
    lanes at once, and more requests than lanes, so that lanes get a second
    occupant whose state must start from zero. Every decode step's logits
    are compared, lane by lane, with the reference's one forward pass over
    the prompt and the tokens served so far."""
    flat, params, mcfg = make(slow_decay=slow_decay)
    eng = engine(params, mcfg)
    assert isinstance(eng.family, NemotronHFamily) and eng.family.rows == 4
    rng = np.random.default_rng(1)
    lengths = (10, ROW, 150, 33, ROW + 1, CALL, 5, CALL + 3)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in lengths]
    futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    seen = {}                 # request -> its steps' logits, in order
    occupants = {}
    calls = []                # (slots, starts, lens) of each prefill call
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
    assert eng.drain(max_steps=500) < 500
    assert max(len(v) for v in occupants.values()) >= 2   # a lane was reused
    # the first call holds three prompts and one empty row is not among
    # them (10, 16 and two rows of the 150); some later call has an empty
    # row beside a prompt, and one holds two prompts
    several = [c for c in calls if len(set(c[0][c[2] > 0].tolist())) >= 2]
    with_empty = [c for c in calls if (c[2] == 0).any() and (c[2] > 0).any()]
    assert several and with_empty
    assert all((c[0][c[2] == 0] == 3).all() for c in calls)  # no slot
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
    # a call that read several prompts counts its time once
    assert 0 < snap["prefill_time_s"] <= snap["admit_time_s"] < (
        snap["loop_busy_s"] - snap["decode_time_s"])
    assert snap["prefill_chunks"] == len(calls)
    assert snap["prefill_chunk_rows"] == sum(
        int((c[2] > 0).sum()) for c in calls)
    assert snap["prefill_positions_run"] == len(calls) * CALL
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
    assert eng.pool.allocations == 2
    eng.drain(max_steps=200)
    alone = engine(params, mcfg)
    fc = alone.submit(b, max_new_tokens=6)
    alone.drain(max_steps=200)
    assert fb.result(timeout=1) == fc.result(timeout=1)
    assert fa.result(timeout=1) != fb.result(timeout=1)
    assert eng.pool.slot_resets == 2


def test_a_call_is_held_until_its_rows_fill_while_lanes_decode():
    """With no lane decoding a call runs at once; while lanes decode, a
    prompt that leaves rows empty waits ``PREFILL_HOLD_STEPS`` steps for
    company, and prompts that fill the rows do not wait."""
    _, params, mcfg = make()
    eng = engine(params, mcfg, max_slots=6)
    eng.submit(np.arange(5) % 96, max_new_tokens=40)
    assert eng.step()["admitted"] == 1      # a step reads prompts, then admits
    assert eng.step()["prefill_chunks"] == 1          # nothing to wait for
    eng.submit(np.arange(20) % 96, max_new_tokens=4)  # 2 of 4 rows
    assert eng.step()["admitted"] == 1
    ran = [eng.step()["prefill_chunks"] for _ in range(PREFILL_HOLD_STEPS + 1)]
    assert ran == [0] * PREFILL_HOLD_STEPS + [1]
    eng.submit(np.arange(40) % 96, max_new_tokens=4)  # 3 rows
    eng.submit(np.arange(17) % 96, max_new_tokens=4)  # 2 rows: 5 >= 4
    assert eng.step()["admitted"] == 2
    assert eng.step()["prefill_chunks"] == 1
    snap = eng.metrics.snapshot()
    # three calls of four rows: 1, 2 and 4 rows carried a prompt
    assert snap["prefill_chunk_rows"] == 1 + 2 + 4
    eng.drain(max_steps=200)


def test_prompt_padding_is_less_than_one_row_a_prompt():
    """One program for every length: 5, 16 and 19 tokens take 1 + 1 + 2
    rows of one call, and no bucket."""
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for n in (5, ROW, ROW + 3):
        eng.submit(np.arange(n) % 96, max_new_tokens=2)
    eng.drain(max_steps=100)
    snap = eng.metrics.snapshot()
    assert snap["prefill_tokens"] == 5 + ROW + ROW + 3
    assert snap["prefill_chunks"] == 1 and snap["prefill_calls"] == 3
    assert snap["prefill_chunk_rows"] == 4
    assert snap["prefill_positions_run"] == CALL


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


# -- (b) the chunked SSD form against the recurrence ------------------------

def _ssd_inputs(rng, T, decay):
    G, J, P, N = 2, 2, 16, 16
    x = jnp.asarray(rng.normal(size=(T, G, J, P)), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(T, G, N)), jnp.float32)
            for _ in range(2))
    rate = {"fast": 0.7, "slow": 0.006}[decay]
    dt = rate * jnp.asarray(rng.uniform(0.5, 1.5, (T, G, J)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.8, 1.2, (G, J)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(G, J)), jnp.float32)
    S0 = jnp.asarray(rng.normal(size=(G, J, P, N)), jnp.float32)
    return x, B, C, dt, A, D, S0


@pytest.mark.parametrize("decay", ["fast", "slow"])
def test_ssd_chunks_match_the_recurrence(decay):
    """Three chunks chained through their end states against the recurrence
    token by token, from a state that is not zero."""
    T = 3 * ROW
    x, B, C, dt, A, D, S0 = _ssd_inputs(np.random.default_rng(0), T, decay)

    def step(S, row):
        return nh.ssm_recurrent_step(S, *row, A, D)

    S_want, y_want = jax.lax.scan(step, S0, (x, B, C, dt))
    S, ys = S0, []
    for c in range(3):
        sl = slice(c * ROW, (c + 1) * ROW)
        y, grow, local = nh.ssd_chunk(x[sl], B[sl], C[sl], dt[sl], A)
        ys.append(y + grow[..., None] * jnp.einsum(
            "gjpn,tgn->tgjp", S, C[sl]) + D[..., None] * x[sl])
        S = grow[-1][..., None, None] * S + local
    np.testing.assert_allclose(jnp.concatenate(ys), y_want, atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(S, S_want, atol=2e-4, rtol=2e-4)
    if decay == "slow":
        # the initial state still weighs in the last chunk's outputs: a
        # state dropped at a chunk boundary would show
        zero, _ = jax.lax.scan(step, jnp.zeros_like(S0), (x, B, C, dt))
        assert float(jnp.abs(zero - S_want).max()) > 1e-2


def test_ssd_padding_leaves_the_state_alone():
    x, B, C, dt, A, D, S0 = _ssd_inputs(np.random.default_rng(1), ROW, "fast")
    dt = jnp.where((jnp.arange(ROW) < 10)[:, None, None], dt, 0.0)
    _, grow, local = nh.ssd_chunk(x, B, C, dt, A)
    S_ten = S0
    for t in range(10):
        S_ten, _ = nh.ssm_recurrent_step(S_ten, x[t], B[t], C[t], dt[t], A, D)
    np.testing.assert_allclose(grow[-1][..., None, None] * S0 + local, S_ten,
                               atol=1e-4, rtol=1e-4)


def test_rows_of_one_prompt_chain_and_other_rows_read_their_slot():
    """Six rows in one mixer call: a prompt of three rows (the last one
    partial) between two others and an empty row. The chained rows give
    what the reference's recurrence gives over the whole prompt; a row of
    another prompt starts from its own slot's state; only the last row of a
    prompt is marked to write."""
    flat, params, mcfg = make(slow_decay=True)
    p = params["layers"]["1"]["mixer"]
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(6, ROW, 64)), jnp.float32)
    slots = jnp.asarray([2, 0, 0, 0, 3, 1], jnp.int32)
    starts = jnp.asarray([32, 0, 16, 32, 0, 16], jnp.int32)
    lens = jnp.asarray([16, 16, 16, 7, 0, 9], jnp.int32)
    follows, last = pl.row_links(slots, starts, lens, ROW)
    assert follows.tolist() == [False, False, True, True, False, False]
    assert last.tolist() == [True, False, False, True, False, True]
    H, P, N = 4, 16, 16
    S_slot = jnp.asarray(rng.normal(size=(6, H, P, N)), jnp.float32)
    S_slot = S_slot.at[1:4].set(0.0)          # the prompt starts from zero
    tail = jnp.asarray(rng.normal(size=(6, 3, mcfg.conv_dim)), jnp.float32)
    tail = tail.at[1:4].set(0.0)
    y, S, new_tail = nh.mamba_prefill(p, mcfg, x, S_slot, tail, lens, follows)
    whole = x[1:4].reshape(3 * ROW, 64)[:2 * ROW + 7]
    want = jax.jit(lambda w, x: ref.mamba_mixer(w, x, ref.dims_of(CFG),
                                                "f32"))(
        weights_mod.flatten(p), whole)
    got = y[1:4].reshape(3 * ROW, 64)[:2 * ROW + 7]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    # row 0 alone, from its own slot's state and tail
    y0, S0, t0 = nh.mamba_prefill(p, mcfg, x[:1], S_slot[:1], tail[:1],
                                  lens[:1], follows[:1])
    np.testing.assert_allclose(y[0], y0[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(S[0], S0[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new_tail[0], t0[0], atol=1e-6)
    # the empty row hands its slot's state and tail through unchanged
    np.testing.assert_allclose(S[4], S_slot[4], atol=1e-6)
    np.testing.assert_allclose(new_tail[4], tail[4], atol=1e-6)


# -- (c) grouped-query decode over pages against the full form --------------

def test_grouped_query_decode_over_pages_matches_the_full_form():
    """The same tokens through the row form (two rows of one prompt) and,
    one token at a time, through decode over the lane's pages: the same
    outputs and the same keys and values in the pages; and against the
    reference's mixer."""
    _, params, mcfg = make()
    p = params["layers"]["6"]["mixer"]
    assert mcfg.layer_kind(6) == "attn"
    rng = np.random.default_rng(2)
    T, pt = 27, 16
    x = jnp.asarray(rng.normal(size=(2, ROW, 64)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(4)[None], jnp.int32)
    pools = (jnp.zeros((1, 6, mcfg.kv_width, pt), jnp.float32),) * 2
    y_pre, k_pre, v_pre = pl.gqa_prefill(
        p, mcfg, x, *pools, 0, jnp.repeat(tables, 2, axis=0),
        jnp.asarray([0, ROW], jnp.int32), jnp.asarray([ROW, T - ROW],
                                                      jnp.int32), pt)
    flat_x = x.reshape(2 * ROW, 64)
    decode = jax.jit(lambda x_t, k, v, t: pl.gqa_decode(
        p, mcfg, x_t, k, v, 0, tables, t, jnp.asarray([True]), pt))
    k_dec, v_dec = pools
    for t in range(T):
        y_t, k_dec, v_dec = decode(flat_x[t:t + 1], k_dec, v_dec,
                                   jnp.asarray([t], jnp.int32))
        np.testing.assert_allclose(y_t[0], y_pre.reshape(2 * ROW, 64)[t],
                                   atol=2e-5, rtol=2e-4)

    def rows(pool):
        return np.asarray(jnp.swapaxes(pool[0, 1:], 1, 2)).reshape(
            -1, mcfg.kv_width)[:T]

    np.testing.assert_allclose(rows(k_dec), rows(k_pre), atol=1e-6)
    np.testing.assert_allclose(rows(v_dec), rows(v_pre), atol=1e-6)
    want = jax.jit(lambda w, x: ref.attention_mixer(
        w, x, ref.dims_of(CFG), "f32"))(weights_mod.flatten(p), flat_x[:T])
    np.testing.assert_allclose(y_pre.reshape(2 * ROW, 64)[:T], want,
                               atol=2e-5, rtol=2e-4)


# -- (c2) decode over ragged lanes: the work list of (lane, block) pairs -----

SPAN = 512                        # keys a pair holds: pl.DECODE_KEY_BLOCK
RAGGED = {
    # name: (positions, active, pairs a tile (None: the rule's), extras)
    "a lane at position 0": ([0, 5, 700], [1, 1, 1], None, False),
    "a block's last key and the next one's first":
        ([511, 512, 513, 1023, 1024], [1] * 5, None, False),
    "an inactive lane between active ones":
        ([600, 300, 40], [1, 0, 1], None, True),
    "one lane 30 blocks long beside lanes of one":
        ([100, 15000, 511, 3], [1] * 4, None, False),
    "more pairs than a tile": ([1500, 15000, 20, 900], [1] * 4, 4, True),
    "a lane's pairs in two tiles": ([1024, 1024, 7], [1] * 3, 2, False),
    "fewer pairs than a tile": ([30, 2], [1, 1], 8, False),
    "no lane active": ([30, 600], [0, 0], None, True),
    "every lane in its last block": ([1535, 1535], [1, 1], None, True),
}


@pytest.mark.parametrize("case", list(RAGGED))
def test_decode_over_ragged_lanes_matches_a_dense_softmax_a_lane(
        case, monkeypatch):
    """``gqa_decode`` over lanes of unlike lengths against each lane's own
    softmax over its keys, all at once in float32: the pool holds random
    keys and values at the lane's positions so far, the step adds its own.
    With ``rotate`` and ``gate`` given (any functions of the right shape)
    and without."""
    positions, active, per_tile, extras = RAGGED[case]
    _, params, mcfg = make()
    p = params["layers"]["6"]["mixer"]
    pt, kvw, hd = 16, mcfg.kv_width, mcfg.head_dim
    if per_tile is not None:
        pair_bytes = 2 * SPAN * kvw * 4
        monkeypatch.setattr(pl, "_TILE_BYTES", per_tile * pair_bytes)
    B = len(positions)
    rng = np.random.default_rng(5)
    owned = [-(-(n + 1) // pt) for n in positions]
    mp = -(-max(owned) // 32) * 32 + 3          # not a whole block of pages
    tables = np.zeros((B, mp), np.int32)
    first = np.cumsum([1] + owned)
    for b in range(B):
        tables[b, :owned[b]] = first[b] + np.arange(owned[b])
    pool = rng.normal(size=(2, 1, first[-1], kvw, pt)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(B, 64)), jnp.float32)
    pos = jnp.asarray(positions, jnp.int32)
    rotate = gate = None
    if extras:
        rotate = lambda q, k, at: (
            q * jnp.cos(0.1 * at)[:, None, None, None],
            k * (1 + jnp.sin(0.01 * at))[:, None])
        gate = lambda ctx: ctx * jnp.linspace(0.5, 1.5, ctx.shape[-1])
    y, k_out, v_out = jax.jit(
        lambda x, k, v: pl.gqa_decode(
            p, mcfg, x, k, v, 0, jnp.asarray(tables), pos,
            jnp.asarray(active, bool), pt, rotate=rotate, gate=gate))(
        x, jnp.asarray(pool[0]), jnp.asarray(pool[1]))
    q, k_new, v_new = pl.gqa_project(p, mcfg, x)
    if rotate is not None:
        q, k_new = rotate(q, k_new, pos)
    for b in range(B):
        if not active[b]:
            continue
        n = positions[b]
        rows = [np.swapaxes(pool[i, 0, tables[b, :owned[b]]], 1, 2).reshape(
            -1, kvw)[:n] for i in (0, 1)]
        keys = np.concatenate([rows[0], np.asarray(k_new[b])[None]])
        vals = np.concatenate([rows[1], np.asarray(v_new[b])[None]])
        keys, vals = (a.reshape(n + 1, -1, hd) for a in (keys, vals))
        s = np.einsum("gjd,ngd->gjn", np.asarray(q[b], np.float64),
                      keys.astype(np.float64)) * hd ** -0.5
        w = np.exp(s - s.max(-1, keepdims=True))
        ctx = np.einsum("gjn,ngd->gjd", w / w.sum(-1, keepdims=True),
                        vals.astype(np.float64)).reshape(-1)
        if gate is not None:
            ctx = np.asarray(gate(jnp.asarray(ctx, jnp.float32)))
        want = ctx.astype(np.float32) @ np.asarray(p["o_proj"]["kernel"])
        np.testing.assert_allclose(y[b], want, atol=2e-5, rtol=2e-4,
                                   err_msg=f"lane {b}")
        # the step's own column, and nothing else of the lane's last page
        page = np.array(k_out[0, tables[b, n // pt]])
        np.testing.assert_allclose(page[:, n % pt], k_new[b], atol=1e-6)
        page[:, n % pt] = pool[0, 0, tables[b, n // pt]][:, n % pt]
        np.testing.assert_array_equal(page, pool[0, 0, tables[b, n // pt]])
    assert np.isfinite(np.asarray(y)).all()


def test_the_work_list_holds_each_active_lanes_blocks_and_no_others():
    """The pairs the decode attention walks are ``sum(ceil((pos + 1) /
    512))`` over the active lanes, lane by lane and block by block; when
    the longest lane grows by a block the list grows by that one pair and
    every other lane's pairs stay as they were (the walk to the longest
    lane's end grew by a block for EVERY lane)."""
    span, nblk, bound = SPAN, 32, 96

    def pairs(positions, active):
        lane, block, live, n = jax.jit(
            pl.decode_work_list, static_argnums=(2, 3, 4))(
                jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
                span, nblk, bound)
        n = int(n)
        assert np.asarray(live).tolist() == [True] * n + [False] * (bound - n)
        assert not np.asarray(lane)[n:].any() and not np.asarray(block)[n:].any()
        return list(zip(np.asarray(lane)[:n].tolist(),
                        np.asarray(block)[:n].tolist()))

    positions, active = [0, 511, 512, 14000, 300, 1023], [1, 1, 1, 1, 0, 1]
    got = pairs(positions, active)
    assert len(got) == sum(-(-(n + 1) // span) for n, a in zip(
        positions, active) if a) == 1 + 1 + 2 + 28 + 2
    assert got == [(b, j) for b, (n, a) in enumerate(zip(positions, active))
                   if a for j in range(n // span + 1)]
    grown = list(positions)
    grown[3] += span
    after = pairs(grown, active)
    assert len(after) == len(got) + 1
    assert [pr for pr in after if pr[0] != 3] == [pr for pr in got
                                                  if pr[0] != 3]
    # a lane past its table's width owns the table's blocks and no more; a
    # list that cannot hold every pair holds the first ``bound``
    assert len(pairs([40000, 10], [1, 1])) == nblk + 1
    assert len(pairs([16383] * 4, [1] * 4)) == bound
    # the tiles the loop runs follow the pairs, not the longest lane
    G = pl.pairs_per_tile(bound, 2 << 20)
    assert G == 16 and pl.pairs_per_tile(8, 1 << 10) == 8
    assert pl.pairs_per_tile(bound, 3 << 20) == 8       # 10 fit: 8 is taken
    assert -(-len(got) // G) == 3 == -(-len(after) // G)


# -- (d) the shares add up to the uncut block -------------------------------

def test_the_two_shares_add_up_to_the_uncut_expert_block():
    """What the shares (0-3) and (4-7) give, with the shared expert counted
    once, adds up to the uncut reference's expert block (all 8 experts)."""
    whole = dict(CFG, n_routed_experts=8)
    flat, params, _ = make(cfg=whole)
    mlp = params["layers"]["2"]["mixer"]
    m = weights_mod.flatten(mlp)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    want = jax.jit(lambda m, x: ref.expert_mixer(m, x, ref.dims_of(whole),
                                                 "f32"))(m, x)
    shared_only = ref._relu2(x, m["shared_experts/up_proj/kernel"],
                             m["shared_experts/down_proj/kernel"], "f32")
    total = jnp.zeros_like(x)
    picks = 0
    for first in (0, 4):
        part = dict(mlp, experts={k: v[first:first + 4]
                                  for k, v in mlp["experts"].items()})
        y, stats = moe_ffn(part, x, k=2, scaling=2.5, renormalize=True,
                           held=(first, 4), tile=8)
        total = total + y
        picks += int(stats[0])
        # the reference, given the same share through the same keys
        half = dict(CFG, share={"n_routed_experts_published": 8,
                                "experts_first": first})
        want_part = jax.jit(lambda m, x, half=half: ref.expert_mixer(
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
    of exactly those two squared-ReLU experts."""
    _, params, _ = make()
    mlp = dict(params["layers"]["4"]["mixer"])
    bias = jnp.zeros(8).at[jnp.asarray([1, 2])].set(10.0)
    mlp["gate"] = dict(mlp["gate"], e_score_correction_bias=bias)
    del mlp["shared_experts"]
    T = 300
    x = jnp.asarray(np.random.default_rng(5).normal(size=(T, 64)), jnp.float32)
    y, stats = moe_ffn(mlp, x, k=2, scaling=2.5, renormalize=True,
                       held=(0, 4), tile=tile)
    assert [int(s) for s in stats] == [2 * T, 2, T]
    idx, w = expert_mod.sigmoid_topk_routing(
        x, mlp["gate"]["kernel"], bias, 2, 2.5)
    assert set(np.asarray(idx).ravel().tolist()) == {1, 2}
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    ex = mlp["experts"]
    want = 0.0
    for j in range(2):
        e = np.asarray(idx[:, j])
        a = jax.nn.relu(jnp.einsum("td,tdf->tf", x, ex["up_proj"][e]))
        want = want + w[:, j:j + 1] * jnp.einsum(
            "tf,tfd->td", a * a, ex["down_proj"][e])
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0      # no all-zero row


# -- (g) each unsupported option raises, by name ----------------------------

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
    "kv_page_tokens=32": dict(kv_page_tokens=32),
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
    assert "nemotron_h" in str(err.value)


def test_fp32_pages_are_refused_for_bfloat16_parameters():
    _, params, mcfg = make()
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    with pytest.raises(UnsupportedOptionError, match="kv_cache_dtype='fp32'"):
        engine(half, mcfg, kv_cache_dtype="fp32")
    eng = engine(half, mcfg, kv_cache_dtype="bf16")
    assert eng.pool.state["ssm"].dtype == jnp.float32    # whatever the type
    assert eng.pool.state["k"].dtype == jnp.bfloat16


def test_handoff_is_refused_by_name():
    _, params, mcfg = make()
    eng = engine(params, mcfg)
    for call in (lambda: eng.handoff_claim(8),
                 lambda: eng.submit_handoff([1, 2, 3], 4),
                 lambda: eng.handoff_install(0, {}, []),
                 lambda: eng.resume_handoff(0, [1], 2, 3)):
        with pytest.raises(UnsupportedOptionError, match="handoff"):
            call()


# -- the state's description and the configuration --------------------------

def test_the_pool_is_the_hybrid_pool_given_the_state_by_description():
    from deepspeed_tpu.inference.serving.kv_pool import HybridStatePool

    _, params, mcfg = make()
    eng = engine(params, mcfg)
    assert type(eng.pool) is HybridStatePool
    st = eng.pool.state
    assert st["ssm"].shape == (4, 3, 4, 16, 16)          # [Lm, slots, H, P, N]
    assert st["conv"].shape == (4, 3, 3, mcfg.conv_dim)
    # two key-value heads side by side in a paged row, tokens last
    assert st["k"].shape == st["v"].shape == (
        1, eng.pool.n_pages, 2 * 16, 16)
    assert eng.pool.paged_names == ("k", "v")


def test_config_reads_the_block_kinds_from_the_published_pattern():
    mcfg = model_config(CFG)
    assert mcfg.pattern == "MEMEM*EME"
    assert [mcfg.layer_kind(i) for i in (1, 2, 6)] == ["mamba", "moe", "attn"]
    assert mcfg.mamba_index == {1: 0, 3: 1, 5: 2, 8: 3}
    assert mcfg.attn_index == {6: 0} and mcfg.n_moe_layers == 4
    assert mcfg.experts_held == (0, 4) and mcfg.n_routed_experts == 8
    assert mcfg.d_inner == 64 and mcfg.conv_dim == 64 + 2 * 2 * 16
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(mcfg, experts_held=(6, 4))
    with pytest.raises(ValueError, match="pattern"):
        dataclasses.replace(mcfg, hybrid_override_pattern="ME-")
    with pytest.raises(ValueError, match="names 3 blocks"):
        dataclasses.replace(mcfg, hybrid_override_pattern="MEM")
    full = nh.NemotronHConfig()
    assert full.num_hidden_layers == 52
    assert (len(full.mamba_index), full.n_moe_layers,
            len(full.attn_index)) == (23, 23, 6)
    assert full.d_inner == 4096 and full.conv_dim == 6144
    assert full.kv_width == 256


def test_reference_lists_leaves_by_block_and_binds_the_configuration():
    shapes = ref.weight_shapes(CFG)
    assert shapes["layers/2/mixer/experts/up_proj"] == (4, 64, 32)
    assert shapes["layers/2/mixer/experts/down_proj"] == (4, 32, 64)
    assert "layers/2/mixer/experts/gate_proj" not in shapes
    assert shapes["layers/2/mixer/gate/kernel"] == (64, 8)
    assert shapes["layers/2/mixer/shared_experts/up_proj/kernel"] == (64, 48)
    assert shapes["layers/1/mixer/in_proj/kernel"] == (64, 64 + 128 + 4)
    assert shapes["layers/1/mixer/conv1d/bias"] == (128,)
    assert shapes["layers/1/mixer/A_log"] == (4,)
    assert shapes["layers/6/mixer/k_proj/kernel"] == (64, 2 * 16)
    assert shapes["layers/6/mixer/q_proj/kernel"] == (64, 4 * 16)
    assert shapes["lm_head/kernel"] == (64, 96)
    with pytest.raises(ValueError, match="n_heads"):
        ref.logits_at({}, None, None, n_heads=5)
