"""``ops/paged_prefill.py``'s kernel against the plain walk it replaces on a
TPU (``models/keye.py::_attend_blocks``), and the two host counters that
say what the kernel's per-row bound saves.

The kernel runs with ``interpret=True`` at sizes that keep what it is built
on, a head of 128 and pages of 128 tokens whose tiles are ``(8, 128)``: 4
key-value heads of 2 query heads each, key blocks of 4 pages, ``topk`` 300.
Both paths are given the same scores a (query, key) and find the selection
from them, so what is compared is the walk: which keys, which pages, which
blocks. Whether the kernel lowers and compiles for the chip is
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

T = PT = HD = 128
KVH, J, BP, TOPK = 4, 2, 4, 300
SPAN = BP * PT
NAN_PAGE = 39           # no row walks it: the table's unused entries name it


def _tables(prompts, blocks, nan_beyond_own):
    """A page table a prompt: its own pages (out of order) as far as its
    deepest row reads, the spare page 0 as far as the call's deepest row
    does (the plain walk runs every row that far, and a masked NaN is still
    a NaN in the values' product), ``NAN_PAGE`` beyond; with
    ``nan_beyond_own`` straight after its own."""
    out = {}
    longest = -(-max(prompts) // SPAN) * BP
    for i, depth in enumerate(prompts):
        used = -(-depth // SPAN) * BP
        own = 1 + 12 * i + np.random.default_rng(i).permutation(12)
        spare = 0 if nan_beyond_own else longest - used
        out[i] = np.r_[own[:used], np.zeros(spare, np.int64),
                       np.full(blocks * BP - used - spare, NAN_PAGE)]
    return out


# a case: (rows as (prompt, start, len), ties). A prompt's depth is its
# deepest row's end.
CASES = {
    # (a) the threshold falls among equal scores: a query with 450 to 900
    # keys behind it takes 300, and a third of them score exactly 0
    "ties_at_the_threshold": ([(0, 512, 128), (0, 640, 128), (0, 768, 128)],
                              True),
    # (b) two prompts at different depths in one call, one under ``topk``
    "prompts_at_different_depths": ([(0, 1024, 128), (0, 1152, 128),
                                     (1, 128, 128), (0, 1280, 128)], False),
    # (c) an empty row, and a row that ends inside its page
    "an_empty_row_and_a_short_one": ([(0, 384, 128), (1, 0, 0),
                                      (0, 512, 77)], True),
    # (d) six blocks in the table, two needed at most: the others' pages
    # hold NaN
    "unused_blocks_hold_nan": ([(0, 384, 128), (0, 512, 128), (1, 0, 100)],
                               False),
}


def _case(rows, ties, blocks=6, nan_beyond_own=False):
    R = len(rows)
    depth = {}
    for prompt, start, n in rows:
        depth[prompt] = max(depth.get(prompt, 0), start + n)
    by_prompt = _tables([depth[i] for i in sorted(depth)], blocks,
                        nan_beyond_own)
    tables = np.stack([by_prompt[prompt] for prompt, _, _ in rows])
    starts = np.array([s for _, s, _ in rows], np.int32)
    lens = np.array([n for _, _, n in rows], np.int32)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(R, T, KVH, J, HD)), jnp.bfloat16)
    pool = rng.normal(size=(2, 40, PT, 2 * KVH, HD)).astype(np.float32)
    pool[:, NAN_PAGE] = np.nan
    S = blocks * SPAN
    s = _scores(5, R * T, S, ties).reshape(R, T, S)
    pos = starts[:, None] + np.arange(T)[None, :]
    s = np.where(np.arange(S)[None, None, :] <= pos[:, :, None], s, -np.inf)
    return (q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(tables, jnp.int32),
            jnp.asarray(starts), jnp.asarray(lens),
            ky._sortable(jnp.asarray(s)), s)


def _both_walks(monkeypatch, q, pool, tables, starts, lens, u):
    """(the kernel's context, the plain walk's) for layer 1 of the pool."""
    args = (q, pool, 1, tables, BP, starts, lens, u, TOPK)
    plain = ky.attend_selected(*args)
    monkeypatch.setattr(paged_prefill, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        paged_prefill, "attend_pages",
        functools.partial(paged_prefill.attend_pages, interpret=True))
    kernel = ky.attend_selected(*args)
    assert kernel.dtype == q.dtype and plain.dtype == jnp.float32
    return (np.asarray(kernel.astype(jnp.float32)),
            np.asarray(plain.astype(q.dtype).astype(jnp.float32)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_gives_the_plain_walks_context(name, monkeypatch):
    q, pool, tables, starts, lens, u, s = _case(*CASES[name])
    got, want = _both_walks(monkeypatch, q, pool, tables, starts, lens, u)
    live = np.asarray(lens) > 0
    assert np.isfinite(got).all()
    # to the rounding of a bfloat16 output (one part in 256)
    np.testing.assert_allclose(got[live], want[live], rtol=2 ** -7, atol=2e-3)
    # an empty row walks no block: its context is zero
    assert not got[~live].any()
    if name == "ties_at_the_threshold":
        # the case is what it says: the 300th largest of most queries' rows
        # is a zero that more keys score than the count has room for
        pos = np.asarray(starts)[:, None] + np.arange(T)
        kth = np.sort(s, axis=-1)[..., -TOPK]
        at, above = (s == kth[..., None]).sum(-1), (s > kth[..., None]).sum(-1)
        assert ((kth == 0) & (above + at > TOPK)).mean() > 0.9
        assert (pos + 1 > TOPK).all()
    if name == "prompts_at_different_depths":
        assert (np.asarray(starts) + np.asarray(lens) <= TOPK).any()
    if name == "unused_blocks_hold_nan":
        # the call's longest row reads two blocks: the other four of every
        # table name the page of NaN
        assert np.isnan(np.asarray(pool[1, NAN_PAGE], np.float32)).all()
        assert (np.asarray(tables)[:, 2 * BP:] == NAN_PAGE).all()


def test_a_rows_walk_ends_at_its_own_last_block(monkeypatch):
    """Rows of a short prompt beside a long one: the kernel walks each to
    its own depth (what lies beyond in the short prompt's table is NaN and
    does not reach its context), where the plain walk runs every row to the
    call's longest and reads it."""
    q, pool, tables, starts, lens, u, _ = _case(
        [(0, 1024, 128), (1, 0, 128)], False, nan_beyond_own=True)
    got, want = _both_walks(monkeypatch, q, pool, tables, starts, lens, u)
    assert np.isfinite(got).all() and np.isnan(want[1]).all()
    np.testing.assert_allclose(got[0], want[0], rtol=2 ** -7, atol=2e-3)


def test_the_prefill_block_counters_are_a_hand_count_of_the_rows():
    """``dsa_prefill_blocks_walked`` / ``_dense`` from a call's ``starts``
    and ``lens``: blocks of 512 keys (4 pages of 128), a layer."""
    metrics = ServingMetrics()
    starts = np.array([0, 128, 1024, 1152, 0, 0], np.int32)
    lens = np.array([128, 50, 128, 1, 0, 0], np.int32)
    keye_family.count_prefill_blocks(metrics, starts, lens, page_tokens=128,
                                     layers=6)
    # rows end at 128, 178, 1152, 1153: 1, 1, 3, 3 blocks; two rows empty
    assert metrics.dsa_prefill_blocks_walked == 6 * (1 + 1 + 3 + 3)
    # the walk in plain operations runs every row of the call, the empty
    # ones too, to the longest row's 3 blocks
    assert metrics.dsa_prefill_blocks_dense == 6 * 6 * 3
    keye_family.count_prefill_blocks(metrics, starts[:2], lens[:2],
                                     page_tokens=128, layers=6)
    snap = metrics.snapshot()
    assert snap["dsa_prefill_blocks_walked"] == 6 * 8 + 6 * 2
    assert snap["dsa_prefill_blocks_dense"] == 6 * 18 + 6 * 2
