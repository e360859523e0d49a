"""``ops/paged_prefill.py::attend_latent`` against the plain walk it replaces
on a TPU (``models/glm_dsa.py::_attend_blocks``), through ``mla_prefill``
itself, and the two host counters that say what the kernel's per-row bound
saves, as GLM-5.2's family records them.

The kernel runs with ``interpret=True`` at sizes that keep what its rule
asks for: pages of 128 tokens, 16 absorbed heads on a latent row of 256 (a
latent of 128 whose values are the row's first 128 columns, a rotated key of
64, padding), key blocks of 4 pages, ``index_topk`` 300. A case is a
``full`` layer's call and, under the selection it hands on, a ``shared``
layer's: both walks are given the same weights and pool and find the same
selection in plain operations, so what is compared is the walk: which keys,
which pages, which blocks. The indexer has ONE head, so half of a query's
index scores are exactly zero and, where the head's weight is positive, a
threshold under ``index_topk`` of twice as many keys falls among equals. Whether the kernel lowers and compiles for
the chip is ``test_kernels_tpu_lowering.py``'s to say.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.families import glm_dsa as glm_family
from deepspeed_tpu.inference.serving.metrics import ServingMetrics
from deepspeed_tpu.models import glm_dsa as gd
from deepspeed_tpu.ops import paged_prefill
# the page tables of Keye-VL's cases: key blocks of 4 pages of 128, a page
# of NaN that no row walks behind the tables' unused entries
from tests.unit.test_keye_prefill_kernel import BP, NAN_PAGE, SPAN, _tables

T = PT = 128
HEADS, RANK, ROPE, NOPE, HIDDEN, QRANK = 16, 128, 64, 16, 64, 32
TOPK = 300
PAGES = NAN_PAGE + 1
CFG = gd.GlmDsaConfig(
    vocab_size=64, hidden_size=HIDDEN, num_hidden_layers=2,
    num_attention_heads=HEADS, num_key_value_heads=HEADS, q_lora_rank=QRANK,
    kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
    qk_head_dim=NOPE + ROPE, v_head_dim=16, index_n_heads=1,
    index_head_dim=64, index_topk=TOPK, indexer_types=("full", "shared"),
    mlp_layer_types=("dense", "dense"), n_routed_experts=4,
    num_experts_per_tok=2)


def _layer(seed, indexer):
    """One layer's attention weights (``self_attn``), bfloat16."""
    rng = np.random.default_rng(seed)

    def w(*shape):
        return {"kernel": jnp.asarray(
            rng.normal(size=shape) * shape[0] ** -0.5, jnp.bfloat16)}

    def ones(n):
        return jnp.ones(n, jnp.bfloat16)

    p = {"q_a_proj": w(HIDDEN, QRANK), "q_a_layernorm": {"scale": ones(QRANK)},
         "q_b_proj": w(QRANK, HEADS * (NOPE + ROPE)),
         "kv_a_proj_with_mqa": w(HIDDEN, RANK + ROPE),
         "kv_a_layernorm": {"scale": ones(RANK)},
         "kv_b_proj": w(RANK, HEADS * (NOPE + 16)),
         "o_proj": w(HEADS * 16, HIDDEN)}
    if indexer:
        p["indexer"] = {"wq_b": w(QRANK, 64), "wk": w(HIDDEN, 64),
                        "k_norm": {"scale": ones(64),
                                   "bias": jnp.zeros(64, jnp.bfloat16)},
                        "weights_proj": w(HIDDEN, 1)}
    return p


# a case: rows as (prompt, start, len). A prompt's depth is its deepest
# row's end.
CASES = {
    # (a) the threshold falls among equal scores: a query with 385 to 640
    # keys behind it takes 300, and half of them score exactly 0; two
    # prompts at one depth
    "ties_at_the_threshold": [(0, 384, 128), (1, 384, 128), (0, 512, 128)],
    # (b) two prompts at different depths in one call, one of them wholly
    # under ``index_topk``; the deep one's rows start in the middle of its
    # table and of a key block
    "prompts_at_different_depths": [(0, 1152, 128), (0, 1280, 128),
                                    (1, 128, 128), (0, 1408, 128)],
    # (c) an empty row, and a row that ends inside its page
    "an_empty_row_and_a_short_one": [(0, 384, 128), (1, 0, 0), (0, 512, 77)],
    # (d) six blocks in the table, two needed at most: the others' pages
    # hold NaN
    "unused_blocks_hold_nan": [(0, 384, 128), (0, 512, 128), (1, 0, 100)],
}


def _case(rows, blocks=6, nan_beyond_own=False):
    R = len(rows)
    depth = {}
    for prompt, start, n in rows:
        depth[prompt] = max(depth.get(prompt, 0), start + n)
    by_prompt = _tables([depth[i] for i in sorted(depth)], blocks,
                        nan_beyond_own)
    tables = np.stack([by_prompt[prompt] for prompt, _, _ in rows])
    rng = np.random.default_rng(11)
    latent = rng.normal(size=(2, PAGES, PT, CFG.latent_row)).astype(np.float32)
    latent[..., CFG.latent_width:] = 0.0
    latent[:, NAN_PAGE] = np.nan
    ik = rng.normal(size=(1, PAGES, 64, PT))
    x = rng.normal(size=(2, R, T, HIDDEN))
    return (jnp.asarray(x, jnp.bfloat16), jnp.asarray(latent, jnp.bfloat16),
            jnp.asarray(ik, jnp.bfloat16), jnp.asarray(tables, jnp.int32),
            jnp.asarray([s for _, s, _ in rows], jnp.int32),
            jnp.asarray([n for _, _, n in rows], jnp.int32))


@jax.jit
def _two_layers(x, latent, ik, tables, starts, lens):
    """A ``full`` layer's attention and a ``shared`` layer's under the
    selection the first hands on: ``(y_full, y_shared, selection)``."""
    y0, latent, ik, selection = gd.mla_prefill(
        _layer(1, True), CFG, x[0], latent, ik, (0, 0), tables, starts, lens,
        PT, None)
    y1, _, _, handed = gd.mla_prefill(
        _layer(2, False), CFG, x[1], latent, ik, (1, None), tables, starts,
        lens, PT, selection)
    assert handed is selection
    return y0, y1, selection


def _both_walks(monkeypatch, *args):
    """((the kernel's outputs, full and shared), (the plain walk's), the
    selection)."""
    plain = _two_layers(*args)
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        paged_prefill, "attend_latent",
        functools.partial(paged_prefill.attend_latent, interpret=True))
    _two_layers.clear_cache()
    try:
        kernel = _two_layers(*args)
    finally:
        _two_layers.clear_cache()
    as_f32 = lambda ys: [np.asarray(y.astype(jnp.float32)) for y in ys[:2]]
    return as_f32(kernel), as_f32(plain), plain[2]


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_the_plain_walks_attention(name, monkeypatch):
    args = _case(CASES[name])
    got, want, selection = _both_walks(monkeypatch, *args)
    starts, lens = np.asarray(args[4]), np.asarray(args[5])
    live = lens > 0
    for kind, g, w in zip(("full", "shared"), got, want):
        assert np.isfinite(g).all(), kind
        # to the rounding of two bfloat16 roundings (the context's, then
        # the output's): the walks differ in the order of float32 sums
        np.testing.assert_allclose(g[live], w[live], rtol=2 ** -6, atol=4e-3,
                                   err_msg=kind)
    u, least, ties_left = (np.asarray(a) for a in selection[:3])
    pos = starts[:, None] + np.arange(T)
    if name == "ties_at_the_threshold":
        # the case is what it says: for the queries whose head weight is
        # positive (half of them: under a negative one the zeros lead the
        # order) the 300th largest score is a zero that more keys hold than
        # the count has room for
        at = (u == least).sum(1)                             # [R, T]
        assert (pos + 1 > TOPK).all()
        assert (at > ties_left[:, 0]).mean() > 0.4
    if name == "prompts_at_different_depths":
        assert (starts + lens <= TOPK).any()
        assert (starts % SPAN != 0).all() and (starts > SPAN).any()
    if name == "unused_blocks_hold_nan":
        # the call's longest row reads two blocks: the other four of every
        # table name the page of NaN
        assert np.isnan(np.asarray(args[1][1, NAN_PAGE], np.float32)).all()
        assert (np.asarray(args[3])[:, 2 * BP:] == NAN_PAGE).all()


def test_a_rows_walk_ends_at_its_own_last_block(monkeypatch):
    """Rows of a short prompt beside a long one: the kernel walks each to
    its own depth (what lies beyond in the short prompt's table is NaN and
    does not reach its context), where the plain walk runs every row to the
    call's longest and reads it."""
    args = _case([(0, 1024, 128), (1, 0, 128)], nan_beyond_own=True)
    got, want, _ = _both_walks(monkeypatch, *args)
    for g, w in zip(got, want):
        assert np.isfinite(g).all() and np.isnan(w[1]).all()
        np.testing.assert_allclose(g[0], w[0], rtol=2 ** -6, atol=4e-3)


def test_the_rule_takes_the_cells_shapes_on_a_tpu_and_nothing_else(
        monkeypatch):
    """``latent_usable`` reads the call and nothing else: off the TPU
    nothing; on it bfloat16 rows of whole lane tiles in pages of 128 that
    are also a row of queries, heads in whole groups."""
    sds = jax.ShapeDtypeStruct
    q, pool = sds((16, 128, 64, 640), jnp.bfloat16), sds(
        (6, 5121, 128, 640), jnp.bfloat16)
    assert not paged_prefill.latent_usable(q, pool, 512)
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    assert paged_prefill.latent_usable(q, pool, 512)
    for bad_q, bad_pool, rank in (
            (sds(q.shape, jnp.float32), sds(pool.shape, jnp.float32), 512),
            (sds((16, 128, 64, 576), jnp.bfloat16),
             sds((6, 5121, 128, 576), jnp.bfloat16), 512),
            (sds((16, 64, 64, 640), jnp.bfloat16),
             sds((6, 5121, 64, 640), jnp.bfloat16), 512),
            (sds((16, 128, 24, 640), jnp.bfloat16), pool, 512),
            (q, pool, 500)):
        assert not paged_prefill.latent_usable(bad_q, bad_pool, rank)


def test_the_prefill_block_counters_are_a_hand_count_of_glms_rows():
    """``dsa_prefill_blocks_walked`` / ``_dense`` as ``GlmDsaFamily`` records
    them from a call's ``starts`` and ``lens``: blocks of 512 keys (4 pages
    of 128), over the six layers that attend (two select, four share)."""
    import types

    family = types.SimpleNamespace(
        loop=types.SimpleNamespace(metrics=ServingMetrics()), row_tokens=128,
        cfg=gd.GlmDsaConfig(
            num_hidden_layers=6, first_layer=2,
            indexer_types=("full",) * 3 + ("shared",) * 3 + ("full",
                                                             "shared")))
    count = functools.partial(glm_family.GlmDsaFamily.count_prefill, family)
    starts = np.array([0, 128, 1024, 1152, 0, 0], np.int32)
    lens = np.array([128, 50, 128, 1, 0, 0], np.int32)
    count(starts, lens)
    metrics = family.loop.metrics
    # rows end at 128, 178, 1152, 1153: 1, 1, 3, 3 blocks; two rows empty
    assert metrics.dsa_prefill_blocks_walked == 6 * (1 + 1 + 3 + 3)
    # the walk in plain operations runs every row of the call, the empty
    # ones too, to the longest row's 3 blocks
    assert metrics.dsa_prefill_blocks_dense == 6 * 6 * 3
    count(starts[:2], lens[:2])
    snap = metrics.snapshot()
    assert snap["dsa_prefill_blocks_walked"] == 6 * 8 + 6 * 2
    assert snap["dsa_prefill_blocks_dense"] == 6 * 18 + 6 * 2
