"""Attention over a paged cache as kernels that read the cache where it
lies. Two are for a cache whose tokens are tiles, a token's key heads and
value heads together as one ``(rows, hd)`` tile, and take a head out of
them in VMEM (``_head``): ``attend_pages``, rows of queries over their
prompts' pages under a mask the caller forms (a prefill call: a block's
scores never leave the chip's VMEM), and ``attend_tiles``, one query a lane
over the tiles a selection gathered (a decode step: the gathered array is
never laid out again). The third, ``attend_latent``, is ``attend_pages``'s
walk for a cache whose tokens are latent rows (absorbed MLA: one row is
every head's key and, its first columns, every head's value), with the
same grid plumbing (``_block_of``, ``_mask_specs``, ``_MASKED``) and a body
of its own: there is no head to unpack.

A chunked prefill call attends ``R`` rows of ``T`` queries, each row to its
own prompt's cached positions, found through the row's page table. Walked in
plain operations (``models/paged_layers.py::gqa_prefill``, and until PR 43
``models/keye.py::dsa_prefill``) a block of keys costs an array of float32
scores with a query axis, a key axis and the heads, which goes through HBM
three times: the walk then runs at the memory's rate and a twentieth of the
matrix unit's (``PERF.md``, PR 42). ``attend_pages`` is the same walk as a
Pallas kernel whose grid is ``(rows, key blocks)``:

- **pages are read where they lie.** ``pool [L, pages, pt, rows, hd]`` holds
  a token as one ``(rows, hd)`` tile, its key heads and then its value
  heads. A grid step fetches the ``block_pages`` pages of a block at the
  indices the row's table gives, as scalars ahead of the grid, each once for
  every head. Head ``h`` of the block is row ``h`` of every token's tile: in
  VMEM the tile's 16-bit rows lie in pairs in 32-bit words, so a strided
  load of the words (one row pair of every token) and a shift give the
  head's ``[span, hd]`` matrix exactly, and no gathered copy exists in HBM;
- **the walk is as long as the row needs.** The grid covers every block of
  the table; ``counts[r]`` blocks are the row's own, a step beyond them does
  nothing and names the block before, so nothing is fetched for it. One
  compiled program whatever the rows hold;
- **the mask is the caller's.** ``allowed(j, *blocks)`` is traced inside the
  kernel and gives ``bool [span, T]`` for key block ``j``, one mask for all
  heads, from the blocks of its own operands: ``keyed`` arrays ``[R, S,
  T]`` are handed a block of ``span`` keys at a time, ``rowed`` arrays ``[R,
  w, T]`` a row at a time, ``shared`` arrays whole. Keys first and the
  row's queries last, because that is how XLA lays an array of scores a
  (query, key) out when it is free to (128 queries are one lane tile, sums
  over keys then run down the sublanes): the operands reach the kernel as
  they lie, without a transposed copy. A causal bound, a window, a
  selection: the kernel knows of none of them;
- **keys run down the sublanes inside the kernel too.** A step's scores are
  ``[span, J x T]``, a group's queries side by side on the lanes (the
  queries go in as ``[hd, J x T]``, the context comes out so, transposed in
  plain operations on either side), so the running maximum and sum are a
  row of ``J x T`` numbers, eight vector registers, where a column would be
  128 of one lane each, and the reductions over keys are elementwise. On
  the chip (``PERF.md``, PR 43) this form took three quarters of the time
  of the one with queries down the sublanes;
- **the mathematics are the walk's**: query-key products of the operands'
  16-bit values accumulated in float32 and scaled there, masked keys at
  ``-1e30``, running maximum, exponent and sums in float32, probabilities
  rounded to the values' type ahead of their product, float32 accumulation,
  one division at the end. Only the context leaves the kernel.

The kernel is for a TPU and for the shapes it was compiled and measured at
(``usable``): 16-bit pages of 128 tokens whose tiles are ``(8, 128)``. Its
plain twin is the caller's own walk (``models/keye.py::dsa_prefill`` keeps
it for every other backend and shape), and ``tests/unit/
test_keye_prefill_kernel.py`` holds the two together with ``interpret=True``.

``attend_latent`` is that walk where the cache holds ONE row a token for
all heads (``models/glm_dsa.py``: ``pool [L, pages, pt, width]``, 512 latent
values, a rotated key of 64 and zeros up to 640; the queries absorbed, 64
heads as wide as the row). In plain operations (``glm_dsa.py::
_attend_blocks``) a block of 512 keys costs a layer the float32 scores and
the float32 accumulator ``[16, 64, 128, 512]`` through HBM, 2.4 ms for 155
GFLOP, a third of the matrix unit (``PERF.md``, PR 50). The kernel's grid is
``(rows, groups of LATENT_HEADS heads, key blocks)``: a step fetches the
block's pages as they lie, ``[span, width]``, which is the key of every
head and whose first ``rank`` columns are the value, so the block is the
matrix unit's stationary operand of both products and neither is
transposed: ``[heads x T, width] x [span, width]^T`` and ``[heads x T, span]
x [span, rank]``, with the scores, the probabilities and the accumulator
``[heads x T, rank]`` in VMEM. **Here the queries run down the sublanes**,
the group's heads one under another, and the maxima and sums over keys run
across the lanes: with 1,152 products a score where ``attend_pages`` has
256 the reductions weigh little, and this form needs no relayout of the
queries (heads ahead of a row's tokens is a move of whole rows) and none of
the context, which leaves the kernel as the caller wants it; on the chip
(``PERF.md``, PR 51) it took 17.7 ms a layer at the cell's shapes where the
form with the queries on the lanes took 19.5, 2.8 of them the two relayouts
around the kernel. The mask, the per-row bound and the mathematics are
``attend_pages``'s word for word, the scale the caller's (it is the
model's ``qk_head_dim``, which the row's width does not show). The pool's
row is a scalar ahead of the grid, so a program's layers share one kernel
body. Which calls take it is ``latent_usable``'s to say, from the call
alone; the plain twin is ``_attend_blocks``, and ``tests/unit/
test_glm_prefill_kernel.py`` holds the two together through
``mla_prefill``.

A decode step attends, a lane, the ``K`` positions its indexer selected,
whose tiles one gather has laid side by side: ``tiles [B, K, rows, hd]``, in
``lax.top_k``'s order, so the positions that are not chosen (a lane that
holds fewer than ``K``) are the tail of the row. In plain operations
(``models/keye.py::attend_chosen``, and until PR 44 ``dsa_decode``) the two
products want the heads ahead of the positions, so each half of the array is
copied to ``[B, KV, K, hd]`` first, a shuffle of sublanes of 134 MB in and
134 MB out (``PERF.md``, PRs 43 and 44: 1.6 ms a layer for the products and
their copies, beside 1.9 for the gather). ``attend_tiles`` is the same
attention as a kernel whose grid is ``(lanes, blocks of TILE_SPAN
positions)``: a step fetches a block of tiles once for all heads (2 MB);
``_head`` gives key head ``g`` and value head ``KV + g`` of it as it gives
them of a page (a block has a page's layout: tokens, rows, ``hd``); the
group's ``J`` queries are the rows of both products, the keys and then the
values the matrix unit's stationary operand, neither transposed; a lane
walks the ``counts[b]`` blocks its selection fills, an inactive lane none,
and a step beyond them fetches nothing; the mathematics are the two
products' (16-bit operands, float32 accumulation and scale, ``-1e30`` where
not chosen, float32 maximum, exponent and sum, running over blocks,
probabilities rounded to the values' type). On the chip (``PERF.md``, PR
44) blocks of 1,024 took four fifths of the time of blocks of 512, and a
draft with no unpacking (the raw block against all the queries, the rows of
other groups masked) the same time at eight times the exponents. For a TPU
and the measured shapes (``tiles_usable``); the plain twin is
``attend_chosen``'s other half, and ``tests/unit/test_keye_decode_kernel.py``
holds the two together.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30             # a key that may not be attended scores this


def _on_tpu():
    return jax.default_backend() == "tpu"


def usable(q, pool):
    """Whether ``attend_pages`` takes ``q [R, T, KV, J, hd]`` over ``pool
    [L, pages, pt, rows, hd]``: on a TPU, 16-bit values, a head of 128, a
    page of 128 tokens that is also a row of queries, and a token's tile of
    8 rows (4 key heads and 4 value heads: one ``(8, 128)`` tile in the
    chip's memory, whose row pairs are its 32-bit words)."""
    return (_on_tpu() and q.dtype == pool.dtype == jnp.bfloat16
            and q.shape[-1] == pool.shape[-1] == 128
            and q.shape[1] == pool.shape[2] == 128
            and pool.shape[3] == 8 == 2 * q.shape[2])


def _block_of(i, j, counts):
    """The block grid step ``(i, j)`` names: row (or lane) ``i``'s ``j``-th,
    and beyond its ``counts[i]`` blocks the last of them again, so that
    nothing is fetched for a step that does nothing."""
    return jnp.maximum(jnp.minimum(j, counts[i] - 1), 0)


def _start(j, m_ref, l_ref, acc_ref):
    """At a row's (or lane's) first block: nothing seen yet."""
    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _emit(j, nb, out_ref, l_ref, acc_ref):
    """At the grid's last block: the one division, and the context out."""
    @pl.when(j == nb - 1)
    def _():
        out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(out_ref.dtype)


def _mask_specs(axes, span, T, keyed, rowed, shared):
    """The block specs of a mask's operands, for a grid of ``axes`` indices
    whose first is the row and whose last the key block, behind the two
    scalars every walk here has ahead of its grid (the tables, then the
    counts): ``keyed`` arrays ``[R, S, T]`` a block of ``span`` keys at a
    time (the block ``_block_of`` names), ``rowed`` arrays ``[R, w, T]`` a
    row at a time, ``shared`` arrays whole."""
    def key_block(*at):
        r, j, cnt = at[0], at[axes - 1], at[axes + 1]
        return r, _block_of(r, j, cnt), 0

    specs = [pl.BlockSpec((None, span, T), key_block) for _ in keyed]
    specs += [pl.BlockSpec((None,) + a.shape[1:], lambda *at: (at[0], 0, 0))
              for a in rowed]
    specs += [pl.BlockSpec(a.shape, lambda *at, nd=a.ndim: (0,) * nd)
              for a in shared]
    return specs


def _head(pages, h):
    """Row ``h`` (an index, traced or not) of every token's tile of the
    block's pages: ``[span, hd]`` float32, exactly. A page's words hold rows
    ``2 i`` (the low half) and ``2 i + 1`` of a tile."""
    shift = (16 * (1 - h % 2)).astype(jnp.uint32)
    out = []
    for ref in pages:
        pt, rows, hd = ref.shape[-3:]
        words = ref.bitcast(jnp.uint32).reshape(pt * rows // 2, hd)[
            pl.ds(h // 2, pt, stride=rows // 2), :]
        out.append(pltpu.bitcast((words << shift) & jnp.uint32(0xFFFF0000),
                                 jnp.float32))
    return jnp.concatenate(out, axis=0)


def attend_pages(q, pool, n, tables, counts, allowed, keyed=(), rowed=(),
                 shared=(), *, block_pages, interpret=False):
    """``q [R, T, KV, J, hd]`` (query head ``(g, i)`` reads key-value head
    ``g``) over row ``n`` of ``pool [L, pages, pt, 2 KV, hd]``; ``tables [R,
    blocks * block_pages]`` the rows' page tables, ``counts [R]`` how many
    key blocks of ``span = block_pages * pt`` positions a row walks (0: the
    row's context is zero). ``allowed(j, *keyed blocks [span, T], *rowed
    blocks [w, T], *shared) -> bool [span, T]`` masks block ``j``; a query no
    key is allowed in its walked blocks reads an average of their values, as
    the plain walk gives it. Returns the context ``[R, KV, J, T, hd]`` in
    ``q``'s type."""
    R, T, kvh, J, hd = q.shape
    pt = pool.shape[2]
    bp = block_pages
    span = bp * pt
    nb = tables.shape[1] // bp
    assert tables.shape[1] == nb * bp and pool.shape[3] == 2 * kvh, (
        tables.shape, bp, pool.shape)
    scale = hd ** -0.5
    rows = J * T

    def kernel(tab_ref, cnt_ref, q_ref, *refs):
        del tab_ref
        pages, refs = refs[:bp], refs[bp:]
        given, refs = refs[:-4], refs[-4:]
        out_ref, m_ref, l_ref, acc_ref = refs
        r, j = pl.program_id(0), pl.program_id(1)

        _start(j, m_ref, l_ref, acc_ref)

        @pl.when(j < cnt_ref[r])
        def _walk():
            ok = allowed(j, *(ref[...] for ref in given))
            # one mask for a group's J heads, whose queries lie side by side
            bias = jnp.tile(jnp.where(ok, 0.0, _MASKED).astype(jnp.float32),
                            (1, J))                          # [span, rows]

            def group(g, _):
                k = _head(pages, g).astype(q_ref.dtype)      # [span, hd]
                v = _head(pages, kvh + g).T.astype(q_ref.dtype)  # [hd, span]
                # a masked key's score is below float32's sight of -1e30,
                # so the sum is -1e30
                s = jnp.dot(k, q_ref[g],
                            preferred_element_type=jnp.float32) * scale + bias
                m = m_ref[g]
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                keep = jnp.exp(m - m_new)
                pr = jnp.exp(s - m_new)
                l_ref[g] = l_ref[g] * keep + jnp.sum(pr, 0, keepdims=True)
                acc_ref[g] = acc_ref[g] * keep + jnp.dot(
                    v, pr.astype(v.dtype), preferred_element_type=jnp.float32)
                m_ref[g] = m_new

            # a loop and not four copies: a quarter of the program's text
            jax.lax.fori_loop(0, kvh, group, None)

        _emit(j, nb, out_ref, l_ref, acc_ref)

    def page(i):
        return pl.BlockSpec(
            (1, 1) + pool.shape[2:],
            lambda r, j, tab, cnt: (n, tab[r, _block_of(r, j, cnt) * bp + i],
                                    0, 0, 0))

    heads = pl.BlockSpec((None, kvh, hd, rows),
                         lambda r, j, tab, cnt: (r, 0, 0, 0))
    in_specs = [heads] + [page(i) for i in range(bp)]
    in_specs += _mask_specs(2, span, T, keyed, rowed, shared)
    ctx = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, kvh, hd, rows), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R, nb), in_specs=in_specs,
            out_specs=heads,
            scratch_shapes=[pltpu.VMEM((kvh, 1, rows), jnp.float32),
                            pltpu.VMEM((kvh, 1, rows), jnp.float32),
                            pltpu.VMEM((kvh, hd, rows), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 2 ** 20),
        interpret=interpret, name="paged_prefill_attention",
    )(tables.astype(jnp.int32), counts.astype(jnp.int32),
      jnp.moveaxis(q, (1, 4), (4, 2)).reshape(R, kvh, hd, rows),
      *([pool] * bp), *keyed, *rowed, *shared)
    return jnp.moveaxis(ctx.reshape(R, kvh, hd, J, T), 2, 4)


TILE_SPAN = 1024            # selected positions in a block of ``attend_tiles``


def tiles_usable(q, tiles):
    """Whether ``attend_tiles`` takes ``q [B, KV, J, hd]`` over ``tiles [B,
    K, rows, hd]``: on a TPU, 16-bit values, a head of 128, a token's tile of
    8 rows (as ``usable`` asks of a page's) and ``K`` whole blocks of
    ``TILE_SPAN`` positions."""
    return (_on_tpu() and q.dtype == tiles.dtype == jnp.bfloat16
            and q.shape[-1] == tiles.shape[-1] == 128
            and tiles.shape[2] == 8 == 2 * q.shape[1]
            and tiles.shape[1] % TILE_SPAN == 0)


def attend_tiles(q, tiles, chosen, counts, *, interpret=False):
    """One query a lane over the tiles its selection fetched, as they lie:
    ``q [B, KV, J, hd]`` (query head ``(g, i)`` reads key-value head ``g``),
    ``tiles [B, K, 2 KV, hd]`` (position ``k`` of lane ``b`` one tile: its key
    heads, then its value heads), ``chosen [B, K]`` which of them are
    attended, ``counts [B]`` how many blocks of ``TILE_SPAN`` positions a
    lane walks (the positions that are not ``chosen`` are the tail of its
    row; 0: the lane's context is zero). Returns the context ``[B, KV, J,
    hd]`` in ``q``'s type."""
    B, kvh, J, hd = q.shape
    K = tiles.shape[1]
    span = TILE_SPAN
    nb = K // span
    assert K == nb * span and tiles.shape[2] == 2 * kvh, (tiles.shape, span)
    scale = hd ** -0.5

    def kernel(cnt_ref, q_ref, bias_ref, tiles_ref, out_ref, m_ref, l_ref,
               acc_ref):
        b, j = pl.program_id(0), pl.program_id(1)

        _start(j, m_ref, l_ref, acc_ref)

        @pl.when(j < cnt_ref[b])
        def _walk():
            bias = bias_ref[...]                             # [1, span]

            def group(g, _):
                k = _head([tiles_ref], g).astype(q_ref.dtype)        # [span, hd]
                v = _head([tiles_ref], kvh + g).astype(q_ref.dtype)
                # the keys are the matrix unit's stationary operand, and
                # then the values: neither is transposed
                s = jax.lax.dot_general(
                    q_ref[g], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale + bias
                m = m_ref[g]                                 # [J, 1]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                keep = jnp.exp(m - m_new)
                pr = jnp.exp(s - m_new)                      # [J, span]
                l_ref[g] = l_ref[g] * keep + jnp.sum(pr, 1, keepdims=True)
                acc_ref[g] = acc_ref[g] * keep + jnp.dot(
                    pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m_ref[g] = m_new

            jax.lax.fori_loop(0, kvh, group, None)

        _emit(j, nb, out_ref, l_ref, acc_ref)

    heads = pl.BlockSpec((None, kvh, J, hd), lambda b, j, cnt: (b, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nb),
            in_specs=[heads,
                      pl.BlockSpec((None, 1, span), lambda b, j, cnt:
                                   (b, 0, _block_of(b, j, cnt))),
                      pl.BlockSpec((1, span) + tiles.shape[2:],
                                   lambda b, j, cnt:
                                   (b, _block_of(b, j, cnt), 0, 0))],
            out_specs=heads,
            scratch_shapes=[pltpu.VMEM((kvh, J, 1), jnp.float32),
                            pltpu.VMEM((kvh, J, 1), jnp.float32),
                            pltpu.VMEM((kvh, J, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="selected_tiles_attention",
    )(counts.astype(jnp.int32), q,
      jnp.where(chosen, 0.0, _MASKED).astype(jnp.float32)[:, None, :], tiles)


LATENT_HEADS = 16           # absorbed query heads a grid step of ``attend_latent``


def latent_usable(q, pool, rank):
    """Whether ``attend_latent`` takes ``q [R, T, heads, width]`` over
    ``pool [L, pages, pt, width]`` with values of ``rank``: on a TPU, 16-bit
    values, a page of 128 tokens that is also a row of queries, a latent
    row and a value width that are whole 128-lane tiles, and heads in whole
    groups of ``LATENT_HEADS``."""
    return (_on_tpu() and q.dtype == pool.dtype == jnp.bfloat16
            and q.shape[1] == pool.shape[2] == 128
            and q.shape[-1] == pool.shape[-1]
            and q.shape[-1] % 128 == 0 and rank % 128 == 0
            and 0 < rank <= q.shape[-1]
            and q.shape[2] % LATENT_HEADS == 0)


def attend_latent(q, pool, n, tables, counts, allowed, keyed=(), rowed=(),
                  shared=(), *, block_pages, rank, scale, interpret=False):
    """``q [R, T, heads, width]`` (absorbed queries: every head reads the
    one key row) over row ``n`` of ``pool [L, pages, pt, width]``, whose
    rows are the keys as they lie and, their first ``rank`` values, the
    values; ``tables``, ``counts``, ``allowed`` and its operands as
    ``attend_pages`` takes them; scores are scaled by ``scale`` in float32.
    A query no key is allowed in its walked blocks reads an average of their
    values, as the plain walk gives it. Returns the weighted latent rows
    ``[R, heads, T, rank]`` in ``q``'s type."""
    R, T, nh, width = q.shape
    pt = pool.shape[2]
    bp = block_pages
    span = bp * pt
    nb = tables.shape[1] // bp
    hg = LATENT_HEADS
    groups = nh // hg
    assert tables.shape[1] == nb * bp and nh == groups * hg, (
        tables.shape, bp, nh, hg)
    rows = hg * T

    def kernel(tab_ref, cnt_ref, row_ref, q_ref, *refs):
        del tab_ref, row_ref
        pages, refs = refs[:bp], refs[bp:]
        given, refs = refs[:-4], refs[-4:]
        out_ref, m_ref, l_ref, acc_ref = refs
        r, j = pl.program_id(0), pl.program_id(2)
        _start(j, m_ref, l_ref, acc_ref)

        @pl.when(j < cnt_ref[r])
        def _walk():
            ok = allowed(j, *(ref[...] for ref in given))
            # one mask for the group's heads, whose queries lie one under
            # another
            bias = jnp.tile(jnp.where(ok, 0.0, _MASKED).astype(jnp.float32).T,
                            (hg, 1))                         # [rows, span]
            # the block is every head's key, and its first columns the
            # value: the stationary operand of both products, as it lies
            lat = jnp.concatenate([ref[0, 0] for ref in pages], axis=0)
            # a masked key's score is below float32's sight of -1e30, so the
            # sum is -1e30
            s = jax.lax.dot_general(
                q_ref[...], lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias
            m = m_ref[...]                                   # [rows, 1]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            keep = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new)
            l_ref[...] = l_ref[...] * keep + jnp.sum(pr, 1, keepdims=True)
            acc_ref[...] = acc_ref[...] * keep + jnp.dot(
                pr.astype(lat.dtype), lat[:, :rank],
                preferred_element_type=jnp.float32)          # [rows, rank]
            m_ref[...] = m_new

        _emit(j, nb, out_ref, l_ref, acc_ref)

    def page(i):
        # the pool's row is a scalar ahead of the grid: one kernel body for
        # every layer of a program
        return pl.BlockSpec(
            (1, 1) + pool.shape[2:],
            lambda r, g, j, tab, cnt, row: (
                row[0], tab[r, _block_of(r, j, cnt) * bp + i], 0, 0))

    def heads(w):
        return pl.BlockSpec((None, None, rows, w),
                            lambda r, g, j, tab, cnt, row: (r, g, 0, 0))

    in_specs = [heads(width)] + [page(i) for i in range(bp)]
    in_specs += _mask_specs(3, span, T, keyed, rowed, shared)
    ctx = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, groups, rows, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(R, groups, nb), in_specs=in_specs,
            out_specs=heads(rank),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, rank), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=96 * 2 ** 20),
        interpret=interpret, name="latent_prefill_attention",
    )(tables.astype(jnp.int32), counts.astype(jnp.int32),
      jnp.full((1,), n, jnp.int32),
      jnp.swapaxes(q, 1, 2).reshape(R, groups, rows, width),
      *([pool] * bp), *keyed, *rowed, *shared)
    return ctx.reshape(R, nh, T, rank)
