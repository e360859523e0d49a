"""``ops/paged_prefill.py::attend_tiles`` against the two products it
replaces on a TPU (the plain half of ``models/keye.py::attend_chosen``), and the
two host counters that say how much of the lanes' rows of selected positions
a decode step walks.

The kernel runs with ``interpret=True`` at sizes that keep what it is built
on, a head of 128 and a token's ``(8, 128)`` tile of 4 key heads and 4 value
heads, 8 query heads a key-value head as the cell has: ``K`` 2,048 selected
positions a lane as there, two blocks of 1,024. Both paths are given the same tiles,
found by the same ``select_topk``, so what is compared is the attention:
which positions, which blocks, which rows of a tile. Whether the kernel
lowers for the chip is ``test_keye.py``'s and
``test_kernels_tpu_lowering.py``'s to say.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving.families import keye as keye_family
from deepspeed_tpu.inference.serving.metrics import ServingMetrics
from deepspeed_tpu.models import keye as ky
from deepspeed_tpu.ops import paged_prefill
from tests.unit.test_keye import _scores

KVH, J, HD, K = 4, 8, 128, 2048
SPAN = paged_prefill.TILE_SPAN
S = 3072                        # slots of a lane's table

# a case: (the lanes' positions, which are active, ties among the scores,
# the selection's size)
CASES = {
    # (a) every lane holds ``K`` positions or more: both blocks, all chosen
    "every_lane_full": ([2900, 2047, 3071], [True, True, True], False, K),
    # (b) lanes under ``K``: 1,500 positions are a whole block and a partly
    # chosen one, 700 a partly chosen block and a skipped one, and one
    "a_lane_under_topk": ([1499, 699, 0], [True, True, True], False, K),
    # (c) an inactive lane between active ones walks nothing
    "an_inactive_lane": ([2700, 1234, 1600], [True, False, True], False, K),
    # (d) a third of the scores are exactly zero, and the 1,024th largest
    # of some 1,900 is one of them
    "ties_at_the_edge": ([1900, 1800, 2047], [True, True, True], True, 1024),
}


def _case(positions, active, ties, K=K, poison_beyond=False):
    B = len(positions)
    positions = np.asarray(positions, np.int32)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, KVH, J, HD)), jnp.bfloat16)
    kv = rng.normal(size=(B, S, 2 * KVH, HD)).astype(np.float32)
    s = _scores(9, B, S, ties)
    at, chosen = ky.select_topk(jnp.asarray(s), jnp.asarray(positions), K)
    tiles = np.take_along_axis(kv, np.asarray(at)[:, :, None, None], axis=1)
    if poison_beyond:
        # what lies beyond a lane's last block of chosen positions
        walked = -(-np.minimum(positions + 1, K) // SPAN)
        for b in range(B):
            tiles[b, walked[b] * SPAN:] = np.nan
    return (q, jnp.asarray(tiles, jnp.bfloat16), chosen,
            jnp.asarray(positions), jnp.asarray(active), s)


def _both(monkeypatch, q, tiles, chosen, positions, active):
    """(the kernel's context, the two products')."""
    plain = ky.attend_chosen(q, tiles, chosen, positions, active)
    with monkeypatch.context() as patch:
        patch.setattr(paged_prefill, "_on_tpu", lambda: True)
        patch.setattr(
            paged_prefill, "attend_tiles",
            functools.partial(paged_prefill.attend_tiles, interpret=True))
        kernel = ky.attend_chosen(q, tiles, chosen, positions, active)
    assert kernel.dtype == q.dtype and plain.dtype == jnp.float32
    assert kernel.shape == plain.shape == q.shape
    return (np.asarray(kernel.astype(jnp.float32)),
            np.asarray(plain.astype(q.dtype).astype(jnp.float32)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_the_two_products_context(name, monkeypatch):
    positions, active, ties, K = CASES[name]
    q, tiles, chosen, pos, act, s = _case(positions, active, ties, K)
    got, want = _both(monkeypatch, q, tiles, chosen, pos, act)
    live = np.asarray(active)
    assert np.isfinite(got).all()
    # to the rounding of a bfloat16 output (one part in 256)
    np.testing.assert_allclose(got[live], want[live], rtol=2 ** -7, atol=2e-3)
    # an inactive lane walks no block: its context is zero
    assert not got[~live].any()
    taken = np.asarray(chosen).sum(-1)
    np.testing.assert_array_equal(taken, np.minimum(np.add(positions, 1), K))
    if name == "a_lane_under_topk":
        assert taken.tolist() == [1500, 700, 1]
    if name == "ties_at_the_edge":
        # the case is what it says: the lanes' ``K``-th largest score is a
        # zero that more positions score than the selection has room for
        for b, p in enumerate(positions):
            row = np.sort(s[b, :p + 1])
            kth = row[-K]
            assert kth == 0 and (row >= kth).sum() > K > (row > kth).sum()


def test_a_lanes_walk_ends_at_its_own_last_block(monkeypatch):
    """Tiles beyond a lane's last block of chosen positions hold NaN: the
    kernel never reads them, where the two products do (a masked score's
    probability is zero, and zero times NaN is NaN)."""
    q, tiles, chosen, pos, act, _ = _case(
        [2900, 699, 300], [True, True, True], False, poison_beyond=True)
    got, want = _both(monkeypatch, q, tiles, chosen, pos, act)
    assert np.isfinite(got).all()
    assert np.isfinite(want[0]).all() and np.isnan(want[1:]).all()
    np.testing.assert_allclose(got[0], want[0], rtol=2 ** -7, atol=2e-3)
    clean = _case([2900, 699, 300], [True, True, True], False)
    again, want = _both(monkeypatch, *clean[:5])
    np.testing.assert_array_equal(got, again)
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2e-3)


def test_dsa_decode_through_the_kernel_is_dsa_decode_through_the_products(
        monkeypatch):
    """A whole layer's decode attention at the widths the kernel takes (32
    query heads on 4 key-value heads of 128, bfloat16, ``topk`` 1,024 over
    tables of 10 pages of 128), lanes over and under ``topk`` and one
    inactive: the output and both pools, kernel against plain path."""
    cfg = ky.KeyeConfig(
        vocab_size=64, hidden_size=256, num_hidden_layers=1,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        indexer_num_heads=4, indexer_head_dim=16, topk=SPAN)
    B, pt, mp, pages, d = 4, 128, 10, 41, cfg.hidden_size
    rng = np.random.default_rng(5)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * shape[0] ** -0.5,
                           jnp.bfloat16)

    heads = cfg.num_attention_heads * cfg.head_dim
    kvw = cfg.num_key_value_heads * cfg.head_dim
    ni, hi = cfg.indexer_num_heads, cfg.indexer_head_dim
    p = {"q_proj": {"kernel": w(d, heads)}, "k_proj": {"kernel": w(d, kvw)},
         "v_proj": {"kernel": w(d, kvw)}, "o_proj": {"kernel": w(heads, d)},
         "q_norm": {"scale": jnp.ones(cfg.head_dim, jnp.bfloat16)},
         "k_norm": {"scale": jnp.ones(cfg.head_dim, jnp.bfloat16)},
         "indexer": {"wq": {"kernel": w(d, ni * hi)},
                     "wk": {"kernel": w(d, hi)},
                     "k_norm": {"scale": jnp.ones(hi, jnp.bfloat16),
                                "bias": jnp.zeros(hi, jnp.bfloat16)},
                     "weights_proj": {"kernel": w(d, ni)}}}
    x = jnp.asarray(rng.normal(size=(B, d)), jnp.bfloat16)
    kv = jnp.asarray(rng.normal(size=(1, pages, pt, 8, 128)), jnp.bfloat16)
    ik = jnp.asarray(rng.normal(size=(1, pages, hi, pt)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(pages - 1)[:B * mp]
                         .reshape(B, mp), jnp.int32)
    positions = jnp.asarray([1100, 100, 511, 1040], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    args = (p, cfg, x, kv, ik, 0, tables, positions, active, pt)
    want = ky.dsa_decode(*args)
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    calls = []

    def kernel(*a, **kw):
        calls.append(a[1].shape)
        return paged_prefill_attend(*a, interpret=True, **kw)

    paged_prefill_attend = paged_prefill.attend_tiles
    monkeypatch.setattr(paged_prefill, "attend_tiles", kernel)
    got = ky.dsa_decode(*args)
    assert calls == [(B, SPAN, 8, 128)]
    live = np.asarray(active)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(got[0])[live], f32(want[0])[live],
                               rtol=2 ** -6, atol=4e-3)
    np.testing.assert_array_equal(f32(got[1]), f32(want[1]))
    np.testing.assert_array_equal(f32(got[2]), f32(want[2]))


def test_the_plain_path_is_taken_wherever_the_kernel_is_not_built_for(
        monkeypatch):
    """Off the TPU, and on it at any other type, head, tile or ``K`` that is
    not whole blocks: ``tiles_usable`` says no and ``attend_chosen`` is the
    two products."""
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    q, tiles = bf(2, KVH, J, HD), bf(2, K, 2 * KVH, HD)
    assert not paged_prefill.tiles_usable(q, tiles)         # the CPU
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    assert paged_prefill.tiles_usable(q, tiles)
    for bad_q, bad_t in [
            (q, bf(2, 24, 2 * KVH, HD)),                    # the tests' topk
            (q, bf(2, K + 512, 2 * KVH, HD)),
            (bf(2, 2, J, HD), bf(2, K, 4, HD)),             # tiles of 4 rows
            (bf(2, KVH, J, 64), bf(2, K, 2 * KVH, 64))]:    # a head of 64
        assert not paged_prefill.tiles_usable(bad_q, bad_t), (bad_q, bad_t)
    assert not paged_prefill.tiles_usable(
        jax.ShapeDtypeStruct(q.shape, jnp.float32),
        jax.ShapeDtypeStruct(tiles.shape, jnp.float32))


def test_the_decode_block_counters_are_a_hand_count_of_the_lanes():
    """``dsa_decode_blocks_walked`` / ``_dense`` from a step's ``held``:
    blocks of 1,024 selected positions, a layer, ``topk`` 2,048 in tables of
    16,384 positions, 8 lanes of which 6 are active."""
    metrics = ServingMetrics()
    held = np.array([0, 1022, 1023, 1024, 2047, 9000], np.int64)
    count = functools.partial(keye_family.count_decode_blocks, metrics,
                              topk=2048, lanes=8, table_positions=16384,
                              layers=6)
    count(held + 1)
    # contexts 1, 1023, 1024, 1025, 2048, 9001 select that many and 2,048
    # at most: 1, 1, 1, 2, 2, 2 blocks
    assert SPAN == 1024
    assert metrics.dsa_decode_blocks_walked == 6 * (1 + 1 + 1 + 2 + 2 + 2)
    # the two products read every lane's 2,048 slots, active or not
    assert metrics.dsa_decode_blocks_dense == 6 * 8 * 2
    count(np.array([1025]))
    snap = metrics.snapshot()
    assert snap["dsa_decode_blocks_walked"] == 6 * 9 + 6 * 2
    assert snap["dsa_decode_blocks_dense"] == 2 * 6 * 8 * 2
    # tables shorter than ``topk``: the program's ``K`` is the table's
    short = ServingMetrics()
    keye_family.count_decode_blocks(short, np.array([300, 1536]), topk=2048,
                                    lanes=3, table_positions=1536, layers=2)
    assert short.dsa_decode_blocks_walked == 2 * (1 + 2)
    assert short.dsa_decode_blocks_dense == 2 * 3 * 2
