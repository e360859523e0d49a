"""What the serving-only decoders share (``kimi_linear``, ``nemotron_h``,
``laguna``, ``mimo_v2``, ``keye``, ``ouro``): the small pieces, rotary positions,
grouped-query attention over a lane's pages, a window layer's ring, and the
walk of a decoder whose layers are full and window ones. Pure functions over
the arrays of a ``HybridStatePool``, no model's own: a model's file imports
this module and none of its siblings, and this module imports none of them.

A paged array is ``[layers, pages, width, page_tokens]``: a page holds its
tokens along the LAST axis and the key-value heads side by side along the
one before (the layout of ``HybridStatePool``'s paged arrays; why it is so is
in ``models/kimi_linear.py``); page 0 is the sink for positions that are not
live. A window layer's ring is ``[layers, slots, W / page_tokens, width,
page_tokens]``: ``W`` positions a lane in blocks laid out as pages are,
tokens last (given the tokens first, XLA re-laid the whole array on the way
into every decode step's scores). Ring slot ``j`` (block ``j / page_tokens``,
column ``j % page_tokens``) of a lane at position ``p`` holds position ``p -
(p - j) mod W``; the mask hides it where that is negative, which is all that
a previous occupant of the lane can have left there. So a lane needs no
reset. Keys are cached rotated.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import paged_decode
from deepspeed_tpu.ops.column_write import write_columns

PREFILL_KEY_BLOCK = 512     # keys a row attends at a time in prefill
DECODE_KEY_BLOCK = 512      # keys a lane attends at a time in decode
_TILE_BYTES = 32 << 20      # keys and values a tile of decode pairs gathers
# a float32 product that the chip does not round to bfloat16 on the way in
EXACT = dict(precision=jax.lax.Precision.HIGHEST,
             preferred_element_type=jnp.float32)


# -- small pieces ---------------------------------------------------------

def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def dot(x, w):
    """``x @ w`` in the parameters' type with float32 accumulation."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def swiglu(x, p):
    """SwiGLU with ``{gate,up,down}_proj/kernel``."""
    a = jax.nn.silu(dot(x, p["gate_proj"]["kernel"])) * dot(
        x, p["up_proj"]["kernel"])
    return dot(a.astype(x.dtype), p["down_proj"]["kernel"]).astype(x.dtype)


def online_softmax_loop(n_blocks, scores_and_values, q_shape, v_width):
    """The skeleton of an attention that walks its keys a block at a time:
    ``scores_and_values(j)`` gives block ``j``'s masked float32 scores
    ``[..., n]`` and a function mapping probabilities to their weighted
    values ``[..., v_width]``."""
    m0 = jnp.full(q_shape, -1e30, jnp.float32)
    l0 = jnp.zeros(q_shape, jnp.float32)
    a0 = jnp.zeros(q_shape + (v_width,), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        s, weigh = scores_and_values(j)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        scale = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None])
        return (m_new, l * scale + jnp.sum(pr, -1),
                acc * scale[..., None] + weigh(pr))

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def lm_head(h, scale, eps, kernel):
    """The final norm and the output head: float32 logits."""
    with jax.named_scope("lm_head"):
        return dot(rms_norm(h, scale, eps), kernel)


# -- rotary positions -------------------------------------------------------

@dataclass(frozen=True)
class RopeSpec:
    """One entry of the published ``rope_parameters``."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: default or yarn")

    @classmethod
    def from_dict(cls, d):
        return cls(**{k: d[k] for k in cls.__dataclass_fields__ if k in d})


def rope_inv_freq(spec, head_dim):
    """``(inv_freq [r / 2] float64, r)`` for the ``r = head_dim x
    partial_rotary_factor`` rotated dimensions. ``default``: ``theta^(-2i /
    r)``. ``yarn`` (as the family's published code computes it): the
    extrapolated frequency where a dimension turns more than ``beta_fast``
    times over the original length, the same over ``factor`` where it turns
    less than ``beta_slow`` times, and a linear ramp between the two."""
    r = int(head_dim * spec.partial_rotary_factor)
    extrap = spec.rope_theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if spec.rope_type == "default":
        return extrap, r
    interp = extrap / spec.factor

    def turns_at(n):        # the dimension that turns n times over the length
        return (r * math.log(spec.original_max_position_embeddings
                             / (2 * math.pi * n))
                / (2 * math.log(spec.rope_theta)))

    low = max(math.floor(turns_at(spec.beta_fast)), 0)
    high = min(math.ceil(turns_at(spec.beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp), r


def apply_rope(spec, x, positions):
    """Rotate the first ``r`` dimensions of each head of ``x [..., hd]`` to
    ``positions`` (one for each leading index of ``x`` up to the heads'
    axes), rotate-half convention: the rotated dimensions split in two
    halves, ``[x1 cos - x2 sin, x2 cos + x1 sin]``; ``cos`` and ``sin``
    times ``attention_factor``. Float32 inside, ``x``'s type out."""
    inv, r = rope_inv_freq(spec, x.shape[-1])
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)
    # the tables first and their broadcast shape after, so that queries and
    # keys of one layer share them (one cos and one sin a position)
    over_heads = positions.shape + (1,) * (x.ndim - 1 - positions.ndim) + (
        r // 2,)
    cos = (jnp.cos(ang) * spec.attention_factor).reshape(over_heads)
    sin = (jnp.sin(ang) * spec.attention_factor).reshape(over_heads)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :r // 2], x32[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., r:]],
        axis=-1).astype(x.dtype)


def rotary(spec, shape, scope):
    """``rotate(q, k, positions)`` by ``spec`` for a layer of ``shape``:
    ``q [..., KV, J, hd]``, ``k [..., KV * hd]`` as ``gqa_project`` gives
    them; traced under the name ``scope``."""
    def rotate(q, k, positions):
        with jax.named_scope(scope):
            heads = k.shape[:-1] + (shape.num_key_value_heads, shape.head_dim)
            return (apply_rope(spec, q, positions),
                    apply_rope(spec, k.reshape(heads), positions).reshape(
                        k.shape))
    return rotate


# -- grouped-query attention over pages --------------------------------------

@dataclass(frozen=True)
class AttentionShape:
    """What the grouped-query and the window functions below read of a
    configuration, for one layer: its query heads, its key-value heads, the
    size of a query or key head and the size of a value head."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    v_head_dim: int


def row_links(slots, starts, lens, row_tokens):
    """Which rows of a prefill call go on from the row before them (the same
    prompt's next ``row_tokens`` tokens), and which are the last of their
    prompt in the call and so write their state back to the slot."""
    follows = jnp.concatenate([jnp.zeros(1, bool), (
        (slots[1:] == slots[:-1]) & (starts[1:] == starts[:-1] + row_tokens)
        & (lens[1:] > 0) & (lens[:-1] == row_tokens))])
    last = (lens > 0) & ~jnp.concatenate([follows[1:], jnp.zeros(1, bool)])
    return follows, last


def gqa_project(p, cfg, x):
    """``q [..., KV, Q/KV, hd]`` (query head ``j`` reads key-value head ``j
    // (Q/KV)``) and the rows cached a token: ``k [..., KV * hd]`` and ``v
    [..., KV * vd]``, each as wide as its projection makes it (``vd =
    cfg.v_head_dim`` may differ from ``hd = cfg.head_dim``)."""
    kvh, hd = cfg.num_key_value_heads, cfg.head_dim
    q = dot(x, p["q_proj"]["kernel"]).astype(x.dtype).reshape(
        x.shape[:-1] + (kvh, cfg.num_attention_heads // kvh, hd))
    k = dot(x, p["k_proj"]["kernel"]).astype(x.dtype)
    v = dot(x, p["v_proj"]["kernel"]).astype(x.dtype)
    return q, k, v


def blocks_of_pages(page_tables, key_block, page_tokens):
    """The page tables padded to whole blocks of ``bp`` pages."""
    mp = page_tables.shape[1]
    bp = max(1, key_block // page_tokens)
    nblk = -(-mp // bp)
    return jnp.pad(page_tables, ((0, 0), (0, nblk * bp - mp))), bp


def gqa_prefill(p, cfg, x, k_pool, v_pool, n, page_tables, starts, lens,
                page_tokens, rotate=None, gate=None):
    """Attention over ``R`` rows: a row's keys and values are written to
    its prompt's pages first (whole pages, each with one in-place update),
    then every query attends the prompt's rows up to its own position, a
    block of pages at a time. ``x [R, T, d]``; ``k_pool`` the whole ``[La,
    pages, KV * hd, page_tokens]`` array, ``v_pool`` the whole ``[La, pages,
    KV * vd, page_tokens]`` one and ``n`` this block's row of them: a Python
    int in a decoder whose layers are unrolled, or a traced int32 scalar in
    one walked by a loop inside the program (``models/ouro.py``), where the
    pools are the loop's carries. Returns ``(y, k_pool, v_pool)``. What a page holds beyond the prompt's end is
    overwritten by decode before it can be attended. ``cfg`` is read for
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (queries
    and keys: the scores are over ``hd``) and ``v_head_dim`` (values: the
    context and the partial sums are over ``vd``) only. A model with
    positions gives ``rotate(q, k, positions) -> (q, k)`` (keys are cached
    rotated), one that multiplies something onto the context ahead of
    ``o_proj`` gives ``gate(ctx [..., Q * vd]) -> ctx`` (``models/
    laguna.py``'s gate a head, ``models/mimo_v2.py``'s value scale);
    without them nothing is traced for either."""
    R, T, _ = x.shape
    kvh, hd, vd = cfg.num_key_value_heads, cfg.head_dim, cfg.v_head_dim
    J = cfg.num_attention_heads // kvh
    pt = page_tokens
    mp = page_tables.shape[1]
    assert T % pt == 0, (T, pt)
    per_row = T // pt
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    q, k, v = gqa_project(p, cfg, x)
    if rotate is not None:
        q, k = rotate(q, k, pos)
    logical = starts[:, None] // pt + jnp.arange(per_row)[None, :]
    dest = jnp.where((lens[:, None] > 0) & (logical < mp),
                     jnp.take_along_axis(
                         page_tables, jnp.clip(logical, 0, mp - 1), 1), 0)

    def as_pages(rows, pool):
        return jnp.swapaxes(rows.astype(pool.dtype).reshape(
            R, per_row, pt, rows.shape[-1]), 2, 3)

    k_new, v_new = as_pages(k, k_pool), as_pages(v, v_pool)

    def put(i, pools):
        r, j = i // per_row, i % per_row
        at = (n, dest[r, j], 0, 0)
        return (jax.lax.dynamic_update_slice(pools[0], k_new[r, j][None, None],
                                             at),
                jax.lax.dynamic_update_slice(pools[1], v_new[r, j][None, None],
                                             at))

    k_pool, v_pool = jax.lax.fori_loop(0, R * per_row, put, (k_pool, v_pool))
    tables, bp = blocks_of_pages(page_tables, PREFILL_KEY_BLOCK, pt)
    end = jnp.max(jnp.where(lens > 0, starts + lens, 0))
    n_blocks = (end + bp * pt - 1) // (bp * pt)
    scale = hd ** -0.5

    def block(j):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        kb = k_pool[n, pages].astype(x.dtype).reshape(R, bp, kvh, hd, pt)
        vb = v_pool[n, pages].astype(x.dtype).reshape(R, bp, kvh, vd, pt)
        kpos = j * bp * pt + jnp.arange(bp * pt)
        s = jnp.einsum("rtgjd,rngdp->rgjtnp", q, kb,
                       preferred_element_type=jnp.float32).reshape(
                           R, kvh, J, T, bp * pt) * scale
        ok = kpos[None, None, None, None, :] <= pos[:, None, None, :, None]
        s = jnp.where(ok, s, -1e30)

        def weigh(pr):
            return jnp.einsum(
                "rgjtnp,rngdp->rgjtd",
                pr.astype(x.dtype).reshape(R, kvh, J, T, bp, pt), vb,
                preferred_element_type=jnp.float32)
        return s, weigh

    ctx = online_softmax_loop(n_blocks, block, (R, kvh, J, T), vd)
    ctx = jnp.moveaxis(ctx, 3, 1).reshape(R, T, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    return (dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            k_pool, v_pool)


# -- attention under a learned selection --------------------------------------
# What the decoders whose attention reads only the positions an indexer
# selects have in common (``keye``, ``glm_dsa``): the index scores, the exact
# top-k two ways (a threshold by bisection for a prefill row's queries,
# ``lax.top_k`` for a decode step's one query a lane) and the page of a chosen
# position. The indexer's keys lie ``[rows, pages, head, page_tokens]``, a
# page's tokens last, because every one of them is read.

def layer_norm(x, p, eps):
    """LayerNorm with ``p["scale"]`` and ``p["bias"]``, float32 out."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def sortable(s):
    """float32 -> int32 whose order, and whose equality, are the floats'
    (the two zeros are one number)."""
    i = jax.lax.bitcast_convert_type(jnp.where(s == 0, 0.0, s), jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


LOWEST = -(2 ** 31)         # below every score's image, -inf's too


_BITS = 3                   # bits of the threshold a pass over the scores fixes


def kth_largest(u, k):
    """The ``k``-th largest of each row of ``u [..., S]`` int32 (``k
    [...]``, at least 1 and at most ``S``), exactly: the value ``t`` with
    ``count(u >= t) >= k > count(u > t)``, found from its highest bit down,
    ``_BITS`` bits a pass. A pass reads the row once and counts it against
    the ``2 ** _BITS - 1`` thresholds that split what is left of the range
    (the passes are bound by reading the scores, not by comparing them, so
    eleven passes of seven counts cost a third of thirty-two of one). Ties
    do not matter to it."""
    lo = jnp.full(k.shape, LOWEST, jnp.int32)    # count(u >= lo) >= k, always
    for left in range(32, 0, -_BITS):            # bits not yet fixed
        take = min(_BITS, left)
        step = 1 << (left - take)
        # the thresholds of one pass, upwards in steps that fit 32 bits: the
        # counts fall as they rise, so the last one that passes stands
        best = t = lo
        for _ in range((1 << take) - 1):
            t = t + step
            enough = jnp.sum((u >= t[..., None]).astype(jnp.int32),
                             axis=-1) >= k
            best = jnp.where(enough, t, best)
        lo = best
    return lo


def index_scores(qI, w, keys):
    """``I [..., S]`` float32 of indexer queries ``qI [..., heads, hi]``
    with head weights ``w [..., heads]`` against ``keys [..., n, hi, p]``
    (``n`` pages of ``p`` tokens, ``S = n p``): the products in the
    operands' type with float32 accumulation, relu, weights and the sum over
    heads in float32."""
    dots = jnp.einsum("...jd,...ndp->...jnp", qI, keys,
                      preferred_element_type=jnp.float32)
    dots = dots.reshape(dots.shape[:-2] + (-1,))
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=-2)


def select_topk(s, positions, topk):
    """The decode step's selection: of ``s [B, S]`` (a score a cached
    position) the ``min(topk, position + 1)`` largest among positions ``0 ..
    position``, exactly, the lower position first among equal scores
    (``lax.top_k``). Returns ``(at [B, K] positions, chosen [B, K] bool)``
    with ``K = min(topk, S)``; where a lane holds fewer than ``K``
    positions the rest of its row is not ``chosen``."""
    held = jnp.arange(s.shape[1])[None, :] <= positions[:, None]
    best, at = jax.lax.top_k(jnp.where(held, s, -jnp.inf),
                             min(topk, s.shape[1]))
    return at, best > -jnp.inf


def pages_of(at, page_tables, page_tokens):
    """The physical page of each position ``at [B, K]`` under ``page_tables
    [B, mp]``, by comparison with every entry of the lane's table (a gather
    of 2,048 single integers a lane takes the chip a millisecond a layer,
    this a fiftieth of it)."""
    mp = page_tables.shape[1]
    return jnp.sum(jnp.where(
        (at // page_tokens)[:, :, None] == jnp.arange(mp)[None, None, :],
        page_tables[:, None, :], 0), axis=-1)


def prefill_key_span(page_tokens):
    """Positions in a key block of the prefill walk (whole pages)."""
    return max(1, PREFILL_KEY_BLOCK // page_tokens) * page_tokens


def index_rows(qI, w, ik_pool, n, tables, bp, pos, n_blocks):
    """A prefill call's index scores: queries ``qI [R, T, heads, hi]`` with
    head weights ``w [R, T, heads]`` at positions ``pos [R, T]`` against row
    ``n`` of ``ik_pool [rows, pages, hi, pt]`` under ``tables [R, blocks *
    bp]``, a block of ``bp`` pages at a time for ``n_blocks`` blocks.
    Returns ``u [R, T, S]`` int32, the scores' sortable images (``S`` the
    tables' positions); a key after its query, and every key beyond the
    blocks scored, holds ``-inf``'s image or less."""
    R, T = pos.shape
    pt = ik_pool.shape[-1]
    span = bp * pt

    def score(j, u):
        pages = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        keys = ik_pool[n, pages].astype(qI.dtype)            # [R, bp, hi, pt]
        s = index_scores(qI, w, keys[:, None])               # [R, T, span]
        kpos = j * span + jnp.arange(span)
        s = jnp.where(kpos[None, None, :] <= pos[:, :, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(u, sortable(s), j * span,
                                                   axis=2)

    return jax.lax.fori_loop(
        0, n_blocks, score,
        jnp.full((R, T, tables.shape[1] * pt), LOWEST, jnp.int32))


def selected(j, uj, least, ties_left, ties_before, below):
    """The selection within key block ``j``, ``bool [span, T]`` (keys first,
    the row's queries last: as ``ops/paged_prefill.py`` takes a mask): a key
    whose score ``uj [span, T]`` is above its query's threshold ``least [1,
    T]``, and of the keys exactly at it the first ``ties_left [1, T]`` of the
    whole row, ``ties_before [blocks, T]`` of which lie in earlier blocks
    (``below [span, span]`` is ``i >= j``: a product with it counts a block's
    ties up to each key, exactly). A key after the query scored ``-inf``,
    which is below every threshold. Traced inside ``paged_prefill``'s
    kernel, and by the plain walks."""
    tie = uj == least
    nth = jnp.dot(below, tie.astype(below.dtype),
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    here = jax.lax.broadcasted_iota(jnp.int32, ties_before.shape, 0) == j
    nth = nth + jnp.sum(jnp.where(here, ties_before, 0), axis=0,
                        keepdims=True)
    return (uj > least) | (tie & (nth <= ties_left))


def row_selection(u, pos, topk, span, dtype):
    """What ``selected`` needs of a prefill call's rows, from their index
    scores ``u [R, T, S]`` (sortable) at positions ``pos [R, T]``: each
    query's ``min(topk, position + 1)``-th largest score (``kth_largest``)
    and how the keys that score exactly that are shared out, lower positions
    first. Returns ``selected``'s operands after ``j``, a row each, keys
    first and the row's queries last: ``(u [R, S, T], least [R, 1, T],
    ties_left [R, 1, T], ties_before [R, blocks, T], below [span, span])``
    with blocks of ``span`` keys."""
    R, T = pos.shape
    take = jnp.minimum(topk, pos + 1)                                # [R, T]
    least = kth_largest(u, take)[..., None]
    # of the keys that score exactly ``least`` only the first few are
    # taken, lower positions first: as many as the keys above it leave
    # of ``take``; counted a block here and a key inside its block
    ties_left = take - jnp.sum((u > least).astype(jnp.int32), axis=-1)
    ties = jnp.sum((u == least).reshape(R, T, -1, span)
                   .astype(jnp.int32), axis=-1)
    ties_before = jnp.cumsum(ties, axis=-1) - ties           # [R, T, blocks]
    # as ``selected`` takes them: keys first, the row's queries last
    return (jnp.swapaxes(u, 1, 2), jnp.swapaxes(least, 1, 2),
            ties_left[:, None, :], jnp.swapaxes(ties_before, 1, 2),
            jnp.tril(jnp.ones((span, span), dtype)))             # [i >= j]


def decode_key_span(page_tokens):
    """Keys in one block of a lane's decode attention: the pages that hold
    ``DECODE_KEY_BLOCK`` tokens, at least one."""
    return max(1, DECODE_KEY_BLOCK // page_tokens) * page_tokens


def decode_work_list(positions, active, span, nblk, bound):
    """The (lane, key block) pairs a decode step attends, lane by lane: an
    active lane at position ``p`` owns blocks ``0 .. p // span`` (at most
    the ``nblk`` its page table holds), an inactive lane none. A prefix sum
    over the lanes' counts lays them out, as ``expert.held_experts_ffn``
    lays out its tiles: ``bound`` pairs of static shape, of which the first
    ``n_pairs`` exist. Returns ``(lane [bound], block [bound], live
    [bound], n_pairs)``; a pair that does not exist reads lane and block
    0."""
    owned = jnp.where(active, jnp.clip(positions // span + 1, 0, nblk), 0)
    lane_end = jnp.cumsum(owned)
    i = jnp.arange(bound)
    n_pairs = jnp.minimum(lane_end[-1], bound)
    live = i < n_pairs
    # the lane whose run of pairs holds i: those that ended at or before it
    lane = jnp.where(live, jnp.searchsorted(lane_end, i, side="right",
                                            method="compare_all"), 0)
    block = jnp.where(live, i - (lane_end - owned)[lane], 0)
    return lane, block, live, n_pairs


def pairs_per_tile(bound, pair_bytes):
    """Pairs one iteration of the decode attention's loop gathers
    (``pair_bytes``: a pair's keys and its values, each at its own width):
    the power of two whose keys and values come nearest ``_TILE_BYTES`` from
    below (an iteration has to move tens of megabytes to stream), and no
    more than the list can hold."""
    g = max(1, _TILE_BYTES // pair_bytes)
    return max(1, min(1 << (g.bit_length() - 1), bound))


def walk_pairs(q, k_pool, v_pool, n, pair_pages, pair_lane, pair_last,
               n_pairs, live, G):
    """The work list of ``decode_work_list`` walked in plain operations, a
    tile of ``G`` pairs an iteration (the list's length a multiple of
    ``G``; ``live [P]`` which pairs exist): the plain twin of
    ``ops/paged_decode.py::attend_pairs``, the same list, the same context
    ``[B, KV, J, vd]`` float32. An iteration gathers its pairs' ``bp``
    pages whole from row ``n`` of the pools and leaves each pair's masked
    partial softmax (running max, sum and weighted values ``[..., vd]``,
    float32); the partials of a lane's pairs, in one tile or in several,
    are combined after the loop."""
    Bn, kvh, J, hd = q.shape
    bound, bp = pair_pages.shape
    pt = k_pool.shape[3]
    vd = v_pool.shape[2] // kvh
    span = bp * pt
    pair_q = q[pair_lane]
    scale = hd ** -0.5

    def tile(i, parts):
        at = i * G
        pages = jax.lax.dynamic_slice_in_dim(pair_pages, at, G)
        qt = jax.lax.dynamic_slice_in_dim(pair_q, at, G)
        last = jax.lax.dynamic_slice_in_dim(pair_last, at, G)
        kb = k_pool[n, pages].astype(q.dtype).reshape(G, bp, kvh, hd, pt)
        vb = v_pool[n, pages].astype(q.dtype).reshape(G, bp, kvh, vd, pt)
        s = jnp.einsum("bgjd,bngdp->bgjnp", qt, kb,
                       preferred_element_type=jnp.float32).reshape(
                           G, kvh, J, span) * scale
        ok = jnp.arange(span)[None, None, None, :] <= last[:, None, None, None]
        m = jnp.max(jnp.where(ok, s, -1e30), axis=-1)
        pr = jnp.where(ok, jnp.exp(s - m[..., None]), 0.0)
        acc = jnp.einsum("bgjnp,bngdp->bgjd",
                         pr.astype(q.dtype).reshape(G, kvh, J, bp, pt), vb,
                         preferred_element_type=jnp.float32)
        return tuple(jax.lax.dynamic_update_slice_in_dim(whole, part, at, 0)
                     for whole, part in zip(parts, (m, jnp.sum(pr, -1), acc)))

    m, l, acc = jax.lax.fori_loop(
        0, (n_pairs + G - 1) // G, tile,
        (jnp.full((bound, kvh, J), -1e30, jnp.float32),
         jnp.zeros((bound, kvh, J), jnp.float32),
         jnp.zeros((bound, kvh, J, vd), jnp.float32)))
    # by lane: the running max, each pair rescaled to it, the sums
    mine = (pair_lane[None, :] == jnp.arange(Bn)[:, None]) & live[None, :]
    m_lane = jnp.max(jnp.where(mine[..., None, None], m[None], -1e30), axis=1)
    w = jnp.exp(m - m_lane[pair_lane])
    l_lane = jnp.einsum("bp,pgj->bgj", mine.astype(jnp.float32), l * w,
                        **EXACT)
    ctx = jnp.einsum("bp,pgjd->bgjd", mine.astype(jnp.float32),
                     acc * w[..., None], **EXACT)
    return ctx / jnp.maximum(l_lane, 1e-30)[..., None]


def gqa_decode(p, cfg, x, k_pool, v_pool, n, page_tables, positions, active,
               page_tokens, rotate=None, gate=None):
    """Attention for one token of every lane over the lane's pages. ``x [B,
    d]``; the new key and value are written at ``positions`` before they are
    attended: a column of each lane's page, all lanes' pages in one
    operation an array (``write_columns``: a page is read once and written
    once, in place; inactive lanes all name the spare page 0; the kernel
    takes ``k`` and ``v [B, width]`` as the projections leave them and turns
    a lane's row into its column itself, so no ``[B, width, 1]`` copy of
    them is made around it). ``rotate``,
    ``gate``, the two head sizes (keys of ``hd``, values of ``vd``) and the
    row ``n`` (a Python int or a traced scalar) as in ``gqa_prefill``. The
    work list depends on the lanes alone, not on ``n``: a caller that loops
    over rows inside the program leaves it to the compiler to keep what no
    iteration changes out of the loop.

    What is walked is the work list of ``decode_work_list``: the (lane,
    block of ``DECODE_KEY_BLOCK`` keys) pairs that exist, so a step reads
    the sum of the lanes' contexts and not every lane up to the longest
    one's end. Where ``ops/paged_decode.py::usable`` takes the shapes (a
    TPU, bfloat16 pools whose 128-token pages hold 128 KB or more) the
    list is walked by one Pallas kernel a call, ``attend_pairs``: it fetches
    a pair's pages itself, those that hold an attended key and no other,
    multiplies under the fetch and carries the softmax across a lane's
    pairs. Everywhere else (the CPU suite, float32 pools, smaller pages:
    Nemotron-H's, which measured slower through the kernel) by
    ``walk_pairs``, its twin in plain operations: a tile of pairs an
    iteration, each pair's pages gathered whole, the pairs' partial
    softmaxes combined by lane after the loop. The pools are only read by
    either."""
    Bn = x.shape[0]
    kvh, hd, vd = cfg.num_key_value_heads, cfg.head_dim, cfg.v_head_dim
    J = cfg.num_attention_heads // kvh
    pt = page_tokens
    mp = page_tables.shape[1]
    logical = jnp.clip(positions // pt, 0, mp - 1)
    phys = jnp.where(active & (positions < mp * pt),
                     page_tables[jnp.arange(Bn), logical], 0)
    q, k, v = gqa_project(p, cfg, x)
    if rotate is not None:
        q, k = rotate(q, k, positions)
    with jax.named_scope("page_write"):
        k_pool = write_columns(k_pool, (n, phys), k, positions % pt)
        v_pool = write_columns(v_pool, (n, phys), v, positions % pt)
    tables, bp = blocks_of_pages(page_tables, DECODE_KEY_BLOCK, pt)
    span, nblk = decode_key_span(pt), tables.shape[1] // bp
    # lanes hold pages of their own, so their blocks are at most the pool's
    # and, a lane, a partial last one and the one a retired lane's step in
    # flight runs past its span
    bound = min(Bn * nblk, -(-k_pool.shape[1] // bp) + 2 * Bn)
    G = pairs_per_tile(
        bound, bp * kvh * (hd + vd) * pt * jnp.dtype(k_pool.dtype).itemsize)
    bound = -(-bound // G) * G
    lane, blk, live, n_pairs = decode_work_list(positions, active, span, nblk,
                                                bound)
    pair_pages = tables.reshape(Bn, nblk, bp)[lane, blk]            # [P, bp]
    # the last key of its block a pair attends; none where there is no pair
    pair_last = jnp.where(live, positions[lane] - blk * span, -1)
    if paged_decode.usable(q, k_pool, v_pool):
        ctx = paged_decode.attend_pairs(q, k_pool, v_pool, n, pair_pages,
                                        lane, pair_last, n_pairs)
    else:
        ctx = walk_pairs(q, k_pool, v_pool, n, pair_pages, lane, pair_last,
                         n_pairs, live, G)
    ctx = ctx.reshape(Bn, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    return (dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            k_pool, v_pool)


# -- window layers: a ring a lane -------------------------------------------


def _sink_softmax(s, sink):
    """Softmax of masked scores ``s [B, KV, J, ..., keys]`` over the keys.
    With ``sink [KV, J]``, one learned logit a query head, the sink joins
    the maximum and the denominator and weighs nothing: the extra column of
    a softmax that is dropped afterwards. (A row that is all mask gives
    zeros then, and not the uniform weights a plain softmax gives; both are
    rows no caller reads.)"""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    with jax.named_scope("attend_window_sink"):
        sink = sink.astype(jnp.float32).reshape(
            sink.shape + (1,) * (s.ndim - 2 - sink.ndim))
        m = jnp.maximum(jnp.max(s, axis=-1), sink)
        e = jnp.exp(s - m[..., None])
        return e / (jnp.sum(e, axis=-1) + jnp.exp(sink - m))[..., None]


def window_prefill(p, shape, x, wk, wv, n, slots, starts, lens, *, window,
                   rotate, gate=None, sink=None):
    """A window layer of ``shape`` (an ``AttentionShape``: keys of ``hd``,
    values of ``vd``) and ``W = window`` over ``R`` rows of ``T`` tokens.
    ``wk`` the whole ``[Lw, slots, W / T, KV * hd, T]`` rings, ``wv`` the
    whole ``[Lw, slots, W / T, KV * vd, T]`` ones and ``n`` this layer's
    row of them; ``W % T == 0`` (``W == T`` is a ring of one block) and
    every ``starts`` a multiple of ``T``, so a row is one block of its
    ring. ``rotate`` and ``gate`` as in ``gqa_prefill``; ``sink
    [KV, J]`` one learned logit a query head that takes part of the
    softmax's mass and adds no value (None: a plain softmax, and nothing
    traced for it). A query at position ``s`` attends to
    positions ``(s - W, s]``: the row's own tokens up to its own, and the
    ``W`` positions before the row, which are the rows of the same prompt
    before it in the call where those reach, and what the lane's ring held
    before the call for the rest (a prompt read in earlier calls; positions
    before the prompt's start are hidden). Afterwards each prompt's last
    ``W`` positions of the call are written to its ring, each row with one
    in-place update. Returns ``(y, wk, wv)``."""
    R, T, _ = x.shape
    W = window
    kvh, hd, vd = (shape.num_key_value_heads, shape.head_dim,
                   shape.v_head_dim)
    J = shape.num_attention_heads // kvh
    assert W % T == 0 and wk.shape[2:] == (W // T, kvh * hd, T) and (
        wv.shape[2:] == (W // T, kvh * vd, T)), (W, T, wk.shape, wv.shape)
    back = W // T                       # rows that reach into a row's window
    pos = starts[:, None] + jnp.arange(T)[None, :]                   # [R, T]
    q, k, v = gqa_project(p, shape, x)
    q, k = rotate(q, k, pos)
    follows, _ = row_links(slots, starts, lens, T)
    # the first row of each row's prompt in this call, and where the call
    # stops reading that prompt
    rows = jnp.arange(R)
    first = jax.lax.cummax(jnp.where(follows, 0, rows))
    stop = jax.ops.segment_max(starts + lens, first, num_segments=R)[first]
    lane = jnp.minimum(slots, wk.shape[1] - 1)
    # block b of the window before row r is row r - back + b of the call
    # where that row is of the same prompt, and else block ((start / T) + b)
    # % back of the ring: position start - W + b T on
    reach = rows[:, None] - back + jnp.arange(back)[None, :]       # [R, back]
    from_call = reach >= first[:, None]
    held_at = (starts[:, None] // T + jnp.arange(back)[None, :]) % back

    def blocks(new, ring, width):
        """``[R, back + 1, KV, width, T]``: the window before each row,
        then the row itself, a block's tokens last as the ring holds
        them."""
        own = jnp.swapaxes(new, 1, 2)                     # [R, KV*width, T]
        in_call = own[jnp.clip(reach, 0, R - 1)]
        held = jnp.take_along_axis(
            ring[n, lane], held_at[:, :, None, None], axis=1).astype(new.dtype)
        before = jnp.where(from_call[:, :, None, None], in_call, held)
        return jnp.concatenate([before, own[:, None]], axis=1).reshape(
            R, back + 1, kvh, width, T), own

    with jax.named_scope("attend_window"):
        keys, k_own = blocks(k, wk, hd)
        vals, v_own = blocks(v, wv, vd)
        s = jnp.einsum("rtgjd,rngdp->rgjtnp", q, keys,
                       preferred_element_type=jnp.float32).reshape(
                           R, kvh, J, T, (back + 1) * T) * hd ** -0.5
        # a key before the row, at index i of W: inside the window of query
        # t where i > t, and a position at all where start - W + i >= 0; a
        # key of the row: causal
        t = jnp.arange(T)[:, None]
        ok = jnp.concatenate([
            jnp.broadcast_to((jnp.arange(W)[None, :] > t)[None], (R, T, W))
            & (starts[:, None, None] - W + jnp.arange(W)[None, None, :] >= 0),
            jnp.broadcast_to((jnp.arange(T)[None, :] <= t)[None], (R, T, T)),
        ], axis=2)
        pr = _sink_softmax(jnp.where(ok[:, None, None], s, -1e30), sink)
        ctx = jnp.einsum(
            "rgjtnp,rngdp->rtgjd",
            pr.astype(x.dtype).reshape(R, kvh, J, T, back + 1, T), vals,
            preferred_element_type=jnp.float32)
    ctx = ctx.reshape(R, T, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    y = dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype)

    # the ring keeps each prompt's last W positions of the call: a row is
    # block (start / T) % back, its tokens that are real and not overwritten
    # by a later row of the same prompt
    keep = (jnp.arange(T)[None, :] < lens[:, None]) & (
        pos >= stop[:, None] - W)
    block_of = (starts // T) % back

    def put(r, rings):
        out = []
        for ring, new in zip(rings, (k_own, v_own)):
            at = (n, lane[r], block_of[r], 0, 0)
            old = jax.lax.dynamic_slice(ring, at,
                                        (1, 1, 1, new.shape[1], T))
            block = jnp.where(keep[r][None, :], new[r].astype(ring.dtype),
                              old[0, 0, 0])
            out.append(jax.lax.dynamic_update_slice(
                ring, block[None, None, None], at))
        return tuple(out)

    wk, wv = jax.lax.fori_loop(0, R, put, (wk, wv))
    return y, wk, wv


def _own_softmax(s, own, sink):
    """Softmax over masked scores ``s [B, KV, J, keys]`` and one more
    column ``own [B, KV, J]``, the score of a key that is not among
    ``s``'s; ``sink [KV, J]`` as in ``_sink_softmax``. Returns the weights
    of ``s``'s keys and the weight of the own column."""
    m = jnp.maximum(jnp.max(s, axis=-1), own)
    rest = 0.0
    if sink is not None:
        with jax.named_scope("attend_window_sink"):
            sink = sink.astype(jnp.float32)
            m = jnp.maximum(m, sink)
            rest = jnp.exp(sink - m)
    e, e_own = jnp.exp(s - m[..., None]), jnp.exp(own - m)
    total = jnp.sum(e, axis=-1) + e_own + rest
    return e / total[..., None], e_own / total


def _ring_write(ring, n, at, new, active):
    """``new [B, width]`` into slot ``at [B]`` of each active lane's ring,
    row ``n`` of ``ring [Lw, slots, back, width, T]``: column ``at % T`` of
    block ``at // T``; an inactive lane's ring stays as it was (its prompt
    may be half read). A ring of one block IS its lane's block, so the
    whole layer's rings take their columns in one pass, read, ``where`` and
    written back at a static index, which XLA does in place: the layer's
    rings move once each way. Of a ring of several blocks only the block
    that holds the slot moves (``write_columns``)."""
    Bn, (back, T) = new.shape[0], (ring.shape[2], ring.shape[4])
    col = jnp.where(active, at % T, -1)
    if back > 1:
        return write_columns(ring, (n, jnp.arange(Bn), at // T), new, col)
    column = jnp.arange(T)[None, :] == col[:, None]
    blocks = jnp.where(column[:, None, None, :], new[:, None, :, None],
                       ring[n, :Bn])
    return jax.lax.dynamic_update_slice(ring, blocks[None], (n, 0, 0, 0, 0))


def window_decode(p, shape, x, wk, wv, n, positions, active, *, window,
                  rotate, gate=None, sink=None):
    """A window layer for one token of every lane (lane ``b`` is slot
    ``b``); ``shape``, ``window``, ``rotate``, ``gate`` and ``sink`` as in
    ``window_prefill``. ``x [B, d]``. The ring is read once, as the step
    found it: slot ``j`` holds position ``p - (p - j) % W``, hidden where
    that is negative and at ``j = p % W``, which still holds ``p - W``; the
    new key and value are one more column of the softmax beside it, so the
    read does not wait for the write. They go to slot ``p % W`` of an
    active lane's ring in one pass a layer and array (``_ring_write``)."""
    Bn = x.shape[0]
    W = window
    kvh, hd, vd = (shape.num_key_value_heads, shape.head_dim,
                   shape.v_head_dim)
    J = shape.num_attention_heads // kvh
    back, T = wk.shape[2], wk.shape[4]
    q, k, v = gqa_project(p, shape, x)
    q, k = rotate(q, k, positions)
    at = positions % W
    k, v = k.astype(wk.dtype), v.astype(wv.dtype)     # as the ring holds them
    with jax.named_scope("attend_window"):
        kb = wk[n, :Bn].astype(x.dtype).reshape(Bn, back, kvh, hd, T)
        vb = wv[n, :Bn].astype(x.dtype).reshape(Bn, back, kvh, vd, T)
        k_own = k.astype(x.dtype).reshape(Bn, kvh, hd)
        v_own = v.astype(x.dtype).reshape(Bn, kvh, vd)
        s = jnp.einsum("bgjd,bngdp->bgjnp", q, kb,
                       preferred_element_type=jnp.float32).reshape(
                           Bn, kvh, J, W) * hd ** -0.5
        s_own = jnp.einsum("bgjd,bgd->bgj", q, k_own,
                           preferred_element_type=jnp.float32) * hd ** -0.5
        held = positions[:, None] - (positions[:, None]
                                     - jnp.arange(W)[None, :]) % W
        ok = (held >= 0) & (jnp.arange(W)[None, :] != at[:, None])
        pr, pr_own = _own_softmax(jnp.where(ok[:, None, None], s, -1e30),
                                  s_own, sink)
        ctx = jnp.einsum("bgjnp,bngdp->bgjd",
                         pr.astype(x.dtype).reshape(Bn, kvh, J, back, T), vb,
                         preferred_element_type=jnp.float32)
        ctx = ctx + (pr_own.astype(x.dtype).astype(jnp.float32)[..., None]
                     * v_own.astype(jnp.float32)[:, :, None, :])
    with jax.named_scope("ring_write"):
        wk = _ring_write(wk, n, at, k, active)
        wv = _ring_write(wv, n, at, v, active)
    ctx = ctx.reshape(Bn, kvh * J * vd)
    if gate is not None:
        ctx = gate(ctx)
    return (dot(ctx.astype(x.dtype), p["o_proj"]["kernel"]).astype(x.dtype),
            wk, wv)


# -- a decoder of full and window layers -------------------------------------

def full_window_prefill_chunk(params, cfg, state, ids, slots, starts, lens,
                              page_tables, *, page_tokens, moe_tile,
                              attention, ffn, eps):
    """``R`` rows of the prompts being read, for a decoder whose layer ``l``
    (from 0) is ``h += Attn_l(RMSNorm(h)); h += FFN_l(RMSNorm(h))``, a full
    layer over row ``cfg.full_index[l]`` of the pages ``k``, ``v`` or, where
    ``cfg.is_window(l)``, a window layer over row ``cfg.window_index[l]`` of
    the rings ``wk``, ``wv``; a final RMSNorm and an untied head. ``ids [R,
    T]`` with ``T = page_tokens``, ``slots [R]`` the slot of each row's
    prompt, ``starts [R]`` tokens of it already read (a multiple of ``T``),
    ``lens [R]`` valid tokens of the row (0: an empty row, which writes
    nothing), ``page_tables [R, mp]``. Rows of one prompt are consecutive
    and in order (``row_links``). A model gives what is its own:
    ``attention(cfg, l, p, x) -> (shape, how)``, the layer's
    ``AttentionShape`` and the keyword arguments of its attention call, and
    ``ffn(lp, cfg, l, x [N, d], live [N], tile, decode) -> (y, counts [3]
    int32)``. Returns ``(state, first [R], logits [R, V])``: the greedy
    token after each row's last valid position (meaningful for the row in
    which a prompt ends), and the logits it was taken from."""
    R, T = ids.shape
    assert T == page_tokens, (T, page_tokens)
    h = params["embed_tokens"]["embedding"][ids]
    live = (jnp.arange(T)[None, :] < lens[:, None]).reshape(R * T)
    k_pool, v_pool, wk, wv = (state[n] for n in ("k", "v", "wk", "wv"))
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = rms_norm(h, lp["input_layernorm"]["scale"], eps)
        p = lp["self_attn"]
        shape, how = attention(cfg, l, p, x)
        if cfg.is_window(l):
            y, wk, wv = window_prefill(p, shape, x, wk, wv,
                                       cfg.window_index[l], slots, starts,
                                       lens, **how)
        else:
            with jax.named_scope("attend_full"):
                y, k_pool, v_pool = gqa_prefill(
                    p, shape, x, k_pool, v_pool, cfg.full_index[l],
                    page_tables, starts, lens, page_tokens, **how)
        h = h + y
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, _ = ffn(lp, cfg, l, x.reshape(R * T, -1), live, moe_tile, False)
        h = h + y.reshape(h.shape)
    at = jnp.clip(lens - 1, 0, T - 1)
    h_last = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
    logits = lm_head(h_last, params["norm"]["scale"], eps,
                     params["lm_head"]["kernel"])
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return {"k": k_pool, "v": v_pool, "wk": wk, "wv": wv}, first, logits


def full_window_decode_step(params, cfg, state, tokens, positions, active,
                            page_tables, *, page_tokens, moe_tile, attention,
                            ffn, eps):
    """One token for every active lane (lane ``b`` is slot ``b``) of the
    decoder ``full_window_prefill_chunk`` reads prompts for, ``attention``
    and ``ffn`` as there. Returns ``(state, tokens, positions, logits [B,
    V], moe [3] int32)``; ``moe`` sums, over this step's expert layers, the
    picks that fell on held experts, the held experts touched and the
    busiest one's tokens (active lanes only)."""
    h = params["embed_tokens"]["embedding"][tokens]
    k_pool, v_pool, wk, wv = (state[n] for n in ("k", "v", "wk", "wv"))
    moe = jnp.zeros(3, jnp.int32)
    for l in range(cfg.num_hidden_layers):
        lp = params["layers"][str(l)]
        x = rms_norm(h, lp["input_layernorm"]["scale"], eps)
        p = lp["self_attn"]
        shape, how = attention(cfg, l, p, x)
        if cfg.is_window(l):
            y, wk, wv = window_decode(p, shape, x, wk, wv,
                                      cfg.window_index[l], positions, active,
                                      **how)
        else:
            with jax.named_scope("attend_full"):
                y, k_pool, v_pool = gqa_decode(
                    p, shape, x, k_pool, v_pool, cfg.full_index[l],
                    page_tables, positions, active, page_tokens, **how)
        h = h + y
        x = rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
        y, counts = ffn(lp, cfg, l, x, active, moe_tile, True)
        moe = moe + counts
        h = h + y
    logits = lm_head(h, params["norm"]["scale"], eps,
                     params["lm_head"]["kernel"])
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = jnp.where(active, nxt, tokens)
    positions = jnp.where(active, positions + 1, positions)
    return ({"k": k_pool, "v": v_pool, "wk": wk, "wv": wv}, tokens,
            positions, logits, moe)
