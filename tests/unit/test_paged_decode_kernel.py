"""``ops/paged_decode.py::attend_pairs`` against the walk in plain operations
it replaces on a TPU (``models/paged_layers.py::walk_pairs``), both through
``gqa_decode`` itself: the projections, the rotation, the page writes, the
work list, the gate and ``o_proj`` are shared, so what is compared is the
walk of the list: which pages, which keys, which head's rows of a page.

The kernel runs with ``interpret=True`` at tiny sizes (pages of 16 tokens,
blocks of 4 pages, heads of 16 and 24), where ``usable`` would say no: the
tests say yes for it. Whether it lowers for the chip at the cells' shapes is
``test_kernels_tpu_lowering.py``'s to say.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import paged_layers as pl
from deepspeed_tpu.ops import paged_decode

PT, BP, MP, D = 16, 4, 10, 32       # a page, pages a block, a lane's table
SPAN = PT * BP

# a call shape: (KV heads, query heads a KV head, hd, vd, rotate, gate, the
# row traced inside a scan)
SHAPES = {
    # one query head a key-value head, the row a loop's traced value (Ouro)
    "one_query_head_traced_row": (4, 1, 16, 16, True, False, True),
    # keys wider than values (MiMo-V2.5)
    "keys_wider_than_values": (2, 3, 24, 16, True, False, False),
    # a gate a head on the context and a rotation of half a head (Laguna)
    "a_gate_and_a_rotation": (2, 3, 16, 16, True, True, False),
    # two key-value heads side by side in a page, no positions (Nemotron-H)
    "two_heads_side_by_side": (2, 4, 16, 16, False, False, False),
}

# a case of lanes: (positions, active)
LANES = {
    "unlike_lengths": ([5, 70, 150, 33], [True] * 4),
    "a_lane_of_one_position": ([0, 40, 0, 100], [True] * 4),
    # 67 = a block and three keys: the second block has one live page of 4
    "one_live_page_of_four": ([67, 3, 131, 64], [True] * 4),
    "a_lane_past_one_block": ([159, 130, 20, 128], [True] * 4),
    "an_inactive_lane": ([90, 75, 12, 140], [True, False, True, True]),
    # the step in flight of a lane retired at its table's end runs past it
    "a_retired_lanes_step_past_its_span": (
        [MP * PT + 2, 30, MP * PT, 9], [True] * 4),
    "every_lane_inactive": ([17, 80, 3, 150], [False] * 4),
}


def _layer(shape_name, dtype, seed=0):
    kvh, J, hd, vd, rotates, gated, traced = SHAPES[shape_name]
    rng = np.random.default_rng(seed)
    B, L = 4, 3
    pages = B * MP + 1
    shape = pl.AttentionShape(kvh * J, kvh, hd, vd)

    def w(*dims):
        return jnp.asarray(rng.normal(size=dims) * dims[0] ** -0.5, dtype)

    p = {"q_proj": {"kernel": w(D, kvh * J * hd)},
         "k_proj": {"kernel": w(D, kvh * hd)},
         "v_proj": {"kernel": w(D, kvh * vd)},
         "o_proj": {"kernel": w(kvh * J * vd, D)}}
    x = jnp.asarray(rng.normal(size=(B, D)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(L, pages, kvh * hd, PT)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(L, pages, kvh * vd, PT)), dtype)
    tables = jnp.asarray(1 + rng.permutation(pages - 1).reshape(B, MP),
                         jnp.int32)
    how = {}
    if rotates:
        how["rotate"] = pl.rotary(
            pl.RopeSpec(rope_theta=100.0, partial_rotary_factor=0.5), shape,
            "rope_full")
    if gated:
        g = jnp.asarray(rng.normal(size=(B, kvh * J)), jnp.float32)
        how["gate"] = lambda ctx: (
            ctx.reshape(B, kvh * J, vd) * jax.nn.sigmoid(g)[..., None]
        ).reshape(ctx.shape)
    return p, shape, x, k_pool, v_pool, tables, how, traced


def _decode(layer, positions, active):
    """``(y, k_pool, v_pool)`` of ``gqa_decode``: row 1 of the pools, or,
    traced, every row in turn inside a ``scan`` with the pools its
    carries."""
    p, shape, x, k_pool, v_pool, tables, how, traced = layer
    positions = jnp.asarray(positions, jnp.int32)
    active = jnp.asarray(active)

    def call(k_pool, v_pool, n):
        return pl.gqa_decode(p, shape, x, k_pool, v_pool, n, tables,
                             positions, active, PT, **how)

    if not traced:
        return call(k_pool, v_pool, 1)

    def row(pools, n):
        y, *pools = call(*pools, n)
        return tuple(pools), y

    (k_pool, v_pool), ys = jax.lax.scan(row, (k_pool, v_pool),
                                        jnp.arange(k_pool.shape[0]))
    return ys, k_pool, v_pool


@pytest.fixture()
def blocks_of_four(monkeypatch):
    monkeypatch.setattr(pl, "DECODE_KEY_BLOCK", SPAN)


def _both(monkeypatch, layer, positions, active):
    """(through the kernel, through the plain walk), and the pairs' lists
    the kernel was handed."""
    plain = _decode(layer, positions, active)
    lists = []

    def kernel(*args, **kw):
        lists.append(args[4:])
        return attend_pairs(*args, interpret=True, **kw)

    attend_pairs = paged_decode.attend_pairs
    with monkeypatch.context() as patch:
        patch.setattr(paged_decode, "usable", lambda *a: True)
        patch.setattr(paged_decode, "attend_pairs", kernel)
        through = _decode(layer, positions, active)
    assert lists and lists[0][0].shape[1] == BP
    f32 = lambda tree: [np.asarray(a.astype(jnp.float32)) for a in tree]
    return f32(through), f32(plain), lists


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lanes", sorted(LANES))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_the_kernel_gives_the_plain_walks_layer(shape_name, lanes, dtype,
                                                blocks_of_four, monkeypatch):
    positions, active = LANES[lanes]
    got, want, _ = _both(monkeypatch, _layer(shape_name, dtype), positions,
                         active)
    assert np.isfinite(got[0]).all()
    # float32 as ``test_ouro.py`` holds its own; bfloat16 to the rounding of
    # a bfloat16 output through ``o_proj``, as the Keye-VL kernels' twins
    tol = (dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32
           else dict(rtol=2 ** -6, atol=4e-3))
    np.testing.assert_allclose(got[0], want[0], **tol)
    # the pools are only read: both paths leave what the page writes left
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    if not any(active):
        assert not got[0].any()


def test_a_dead_pages_content_cannot_reach_a_live_lanes_context(
        blocks_of_four, monkeypatch):
    """The spare page 0, which every unused slot of a block names, holds
    NaN: the plain walk gathers it (a masked key's weight is zero, and zero
    times NaN is NaN) with the last block of every lane that does not fill
    it, the kernel fetches no page without an attended key."""
    positions, active = [67, 3, 131, 63], [True] * 4
    p, shape, x, k_pool, v_pool, tables, how, traced = _layer(
        "keys_wider_than_values", jnp.float32)
    # a lane holds the pages of its positions and no more, as the allocator
    # leaves its table
    tables = jnp.where(jnp.arange(MP)[None, :]
                       <= jnp.asarray(positions)[:, None] // PT, tables, 0)
    clean = (p, shape, x, k_pool, v_pool, tables, how, traced)
    dirty = (p, shape, x, k_pool.at[:, 0].set(jnp.nan),
             v_pool.at[:, 0].set(jnp.nan), tables, how, traced)
    got, want, lists = _both(monkeypatch, dirty, positions, active)
    assert np.isfinite(got[0]).all()
    # (every lane's: the by-lane combine sums all pairs, the others' at
    # weight zero)
    assert np.isnan(want[0]).all()
    again, want, _ = _both(monkeypatch, clean, positions, active)
    np.testing.assert_array_equal(got[0], again[0])
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    # the case is what it says: pairs whose block holds one live page
    pages, _, last, n_pairs = (np.asarray(a) for a in lists[0])
    live = np.minimum(last[:int(n_pairs)] // PT, BP - 1) + 1
    assert sorted(live.tolist()) == [1, 1, 1, 4, 4, 4, 4]
    assert (pages[:int(n_pairs)][live == 1][:, 1:] == 0).all()


def test_the_plain_walk_is_taken_wherever_the_kernel_is_not_built_for(
        monkeypatch):
    """Off the TPU, and on it at any other type, page or width: ``usable``
    says no and ``gqa_decode`` walks in plain operations."""
    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    bf = functools.partial(sds, jnp.bfloat16)
    cells = {       # q, a page of keys and of values, as the cells have them
        "ouro": (bf(8, 16, 1, 128), bf(192, 41, 2048, 128),
                 bf(192, 41, 2048, 128)),
        "mimo_v2": (bf(128, 4, 16, 192), bf(2, 4097, 768, 128),
                    bf(2, 4097, 512, 128)),
        "laguna_48": (bf(64, 8, 6, 128), bf(2, 3585, 1024, 128),
                      bf(2, 3585, 1024, 128)),
        "laguna_64": (bf(64, 8, 8, 128), bf(2, 3585, 1024, 128),
                      bf(2, 3585, 1024, 128)),
        "nemotron_h": (bf(128, 2, 16, 128), bf(1, 3073, 256, 128),
                       bf(1, 3073, 256, 128)),
    }
    for name, call in cells.items():
        assert not paged_decode.usable(*call), name         # the CPU
    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: True)
    for name, call in cells.items():
        # pages of 64 KB measured slower through the kernel (PERF.md, PR 47)
        assert paged_decode.usable(*call) == (name != "nemotron_h"), name
    q, k, v = cells["laguna_64"]
    f32 = functools.partial(sds, jnp.float32)
    for bad in [
            (f32(*q.shape), f32(*k.shape), f32(*v.shape)),  # float32 pools
            (q, bf(2, 3585, 1024, 16), bf(2, 3585, 1024, 16)),  # the tests'
            (bf(64, 8, 8, 24), bf(2, 3585, 192, 128), v),   # keys of 24
            (q, k, bf(2, 3585, 512, 128)),                  # values of 64
            (bf(64, 2, 8, 128), bf(2, 3585, 256, 128),      # pages of 64 KB
             bf(2, 3585, 256, 128)),
            (bf(64, 8, 1, 128), k, v)]:                     # 8 rows of heads
        assert not paged_decode.usable(*bad), bad
