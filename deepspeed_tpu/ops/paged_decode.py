"""One token a lane over the lane's pages, as one kernel that fetches the
pages itself: the body of ``models/paged_layers.py::gqa_decode``'s walk.

A decode step attends, a lane, the keys and values its page table names, up
to the lane's position. ``gqa_decode`` lays that work out as a list of
(lane, block of ``bp`` pages) pairs, lane by lane (``decode_work_list``).
Walked in plain operations a tile of pairs is gathered whole from the pool
by two XLA gathers and only then multiplied: a pair is fetched whole though
its lane may fill one page of its ``bp``, and the arithmetic waits for the
gather. ``attend_pairs`` is the same walk as ONE Pallas kernel a call:

- **a page no key of which is attended is not fetched.** The pools stay in
  HBM and the kernel copies pages itself: of pair ``i`` the pages ``j`` with
  ``j * pt <= last[i]``, each one DMA of its ``[width, pt]`` block of row
  ``n`` where it lies, into one of two sets of ``bp`` buffers. What the
  unused slots of a block name (the spare page 0) is never read, and a pair
  beyond ``n_pairs`` costs nothing: the loop over the list runs ``n_pairs``
  times, not the list's static length;
- **arithmetic under the fetch.** Pair ``i + 1``'s copies are started before
  pair ``i`` is computed, and pair ``i`` waits page by page, so the first
  page's arithmetic starts when that page has landed and not the pair;
- **pages are multiplied as they lie.** Tokens are last in a page, so a
  page of keys ``[KV * hd, pt]`` is the matrix unit's stationary operand
  untransposed. The lane's queries are laid out block-diagonal once a
  lane, in VMEM: ``[KV * J, KV * hd]`` with head ``(g, i)``'s query in row
  ``g J + i`` under key head ``g``'s rows and zeros elsewhere (the zeros
  add nothing to a float32 sum; formed in plain operations ahead of the
  kernel the same array cost Ouro's layer call seven small fusions), so ONE
  product a page gives every head's scores ``[KV * J, pt]`` and the softmax
  runs over all heads at once; the values' product (``[KV * J, pt]`` by the
  page ``[KV * vd, pt]``, contracted over the tokens) likewise, and head
  ``g``'s context is block ``g`` of its rows' sums, taken once a lane;
- **the running softmax is carried across a lane's pairs**, which are
  consecutive in the list: one context a lane leaves the kernel, and the
  walk's per-pair partial buffers and its by-lane combine are not there. A
  lane that owns no pair reads zeros, as the walk gives it;
- **the mathematics are the walk's**: 16-bit operands, float32 accumulation
  and scale, ``-1e30`` where ``key > last``, float32 maximum, exponent and
  sum, probabilities rounded to the values' type ahead of their product,
  one division at the end;
- **the pools are only read**: they are the caller's donated carries and no
  operation of pool shape exists beside them.

The kernel is for a TPU and the shapes it was compiled and measured at
(``usable``; ``PERF.md``, PR 47, one list at each cell's shapes, the kernel
against the plain walk): bfloat16 pools of 128-token pages, value heads of a
multiple of 128; Ouro's ``J = 1`` on 16 key-value heads (pages of 512 KB:
0.49 of the walk's time, and 26.9 us a layer call in the cell, 87% of 819
GB/s over the live pages), Laguna's 6 or 8 query heads a key-value head
(``[1024, 128]``: 0.70 and 0.75), MiMo-V2.5's keys of 192 and values of 128
(``[768, 128]`` and ``[512, 128]``: 0.92). A page costs the kernel 0.6-0.7
us whatever its size (its chain of products, maximum, exponent and sums
waits on each unit's latency in turn), so Nemotron-H's pages of 64 KB
(``[256, 128]``) took 1.82 of the walk's time and ``usable`` leaves them,
and every other backend and shape, to the plain twin, the walk that stays
in ``models/paged_layers.py::walk_pairs``;
``tests/unit/test_paged_decode_kernel.py`` holds the two together with
``interpret=True``.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30             # a key that may not be attended scores this
# the smallest page ``usable`` takes: 512 rows of 128 bfloat16 tokens are 128
# KB. Measured (``PERF.md``, PR 47): MiMo-V2.5's pages of 192 and 128 KB
# 0.92 of the plain walk's time a call, Nemotron-H's of 64 KB 1.82
_PAGE_ROWS = 512


def _on_tpu():
    return jax.default_backend() == "tpu"


def usable(q, k_pool, v_pool):
    """Whether ``attend_pairs`` takes ``q [B, KV, J, hd]`` over ``k_pool
    [L, pages, KV * hd, pt]`` and ``v_pool [L, pages, KV * vd, pt]``: on a
    TPU, bfloat16 throughout, pages of 128 tokens, rows of keys in whole
    lane tiles (``KV * hd`` a multiple of 128), value heads of a multiple
    of 128, the heads' rows ``KV * J`` in whole 16-row tiles, and pages of
    ``_PAGE_ROWS`` rows or more: a page costs the kernel some 0.6-0.7 us
    whatever it holds, which a page of 128 KB hides and one of 64 KB does
    not."""
    kvh, J = q.shape[1], q.shape[2]
    return (_on_tpu() and q.dtype == k_pool.dtype == v_pool.dtype
            == jnp.bfloat16 and k_pool.shape[3] == v_pool.shape[3] == 128
            and k_pool.shape[2] % 128 == 0
            and v_pool.shape[2] % (128 * kvh) == 0 and (kvh * J) % 16 == 0
            and min(k_pool.shape[2], v_pool.shape[2]) >= _PAGE_ROWS)


def attend_pairs(q, k_pool, v_pool, n, pair_pages, pair_lane, pair_last,
                 n_pairs, *, interpret=False):
    """``q [B, KV, J, hd]`` (query head ``(g, i)`` reads key-value head
    ``g``) over row ``n`` (a Python int or a traced scalar) of ``k_pool [L,
    pages, KV * hd, pt]`` and ``v_pool [L, pages, KV * vd, pt]``, along the
    work list of ``decode_work_list``: pair ``i < n_pairs`` is lane
    ``pair_lane[i]``'s block of pages ``pair_pages[i] [bp]``, of whose keys
    those up to ``pair_last[i]`` (an index into the block, at least 0) are
    attended; a lane's pairs are consecutive. Returns the context ``[B, KV,
    J, vd]`` float32, zeros for a lane without a pair."""
    B, kvh, J, hd = q.shape
    W, pt = k_pool.shape[2:]
    Wv = v_pool.shape[2]
    vd = Wv // kvh
    P, bp = pair_pages.shape
    R = kvh * J
    assert W == kvh * hd and Wv == kvh * vd and v_pool.shape[3] == pt, (
        q.shape, k_pool.shape, v_pool.shape)
    scale = hd ** -0.5
    # a lane's queries are copied in whole lane tiles
    q_rows = jnp.pad(q.reshape(B, R, hd), ((0, 0), (0, 0), (0, -hd % 128)))

    def kernel(row_ref, pages_ref, lane_ref, last_ref, count_ref, q_hbm,
               k_hbm, v_hbm, out_ref, qbuf, kbuf, vbuf, qsem, ksem, vsem,
               q_ref, m_ref, l_ref, acc_ref):
        n_row, count = row_ref[0], count_ref[0]
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

        def heads_own(width):
            """``g -> bool [R, width]``: the rows of key-value head ``g``'s
            query heads."""
            row = jax.lax.broadcasted_iota(jnp.int32, (R, width), 0)
            return lambda g: (row >= g * J) & (row < (g + 1) * J)

        def page_copies(i, slot, j):
            page = pages_ref[i * bp + j]
            return (pltpu.make_async_copy(k_hbm.at[n_row, page],
                                          kbuf.at[slot, j], ksem.at[slot, j]),
                    pltpu.make_async_copy(v_hbm.at[n_row, page],
                                          vbuf.at[slot, j], vsem.at[slot, j]))

        def fetch(i, slot):
            for j in range(bp):
                @pl.when(j * pt <= last_ref[i])
                def _start():
                    for copy in page_copies(i, slot, j):
                        copy.start()

        def q_copy(lane, slot):
            return pltpu.make_async_copy(q_hbm.at[lane], qbuf.at[slot],
                                         qsem.at[slot])

        @pl.when(count > 0)
        def _prime():
            q_copy(lane_ref[0], 0).start()
            fetch(0, 0)

        def pair(i, qslot):
            slot = i % 2
            lane, last = lane_ref[i], last_ref[i]
            nxt = jnp.minimum(i + 1, P - 1)
            more = i + 1 < count
            turn = more & (lane_ref[nxt] != lane)   # the next pair's lane
            first = (i == 0) | (lane_ref[jnp.maximum(i - 1, 0)] != lane)

            @pl.when(turn)
            def _next_lane():
                q_copy(lane_ref[nxt], 1 - qslot).start()

            @pl.when(more)
            def _next_pair():
                fetch(nxt, 1 - slot)

            @pl.when(first)
            def _start():
                q_copy(lane, qslot).wait()
                # row g J + i: head (g, i)'s query under key head g's rows
                # of a page, zeros under the other heads' (float32 on the
                # way: a select of 16-bit values is no vector operation on
                # a v5e)
                own = heads_own(hd)
                rows = qbuf[qslot][:, :hd].astype(jnp.float32)
                for g in range(kvh):
                    q_ref[:, g * hd:(g + 1) * hd] = jnp.where(
                        own(g), rows, 0.0).astype(q_ref.dtype)
                m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
                l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
                acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

            for j in range(bp):
                @pl.when(j * pt <= last)
                def _page():
                    k_copy, v_copy = page_copies(i, slot, j)
                    k_copy.wait()
                    s = jnp.dot(q_ref[...], kbuf[slot, j],
                                preferred_element_type=jnp.float32) * scale
                    key = j * pt + jax.lax.broadcasted_iota(
                        jnp.int32, (R, pt), 1)
                    # the page's first key is attended, so the maximum is
                    # a score and a masked key's weight is exactly zero
                    s = jnp.where(key <= last, s, _MASKED)
                    m = m_ref[...]
                    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                    keep = jnp.exp(m - m_new)
                    pr = jnp.exp(s - m_new)                     # [R, pt]
                    l_ref[...] = l_ref[...] * keep + jnp.sum(
                        pr, axis=1, keepdims=True)
                    m_ref[...] = m_new
                    v_copy.wait()
                    acc_ref[...] = acc_ref[...] * keep + jax.lax.dot_general(
                        pr.astype(vbuf.dtype), vbuf[slot, j],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)     # [R, Wv]

            @pl.when(~more | turn)
            def _emit():
                own = heads_own(vd)
                ctx = jnp.zeros((R, vd), jnp.float32)
                for g in range(kvh):
                    ctx = jnp.where(own(g), acc_ref[:, g * vd:(g + 1) * vd],
                                    ctx)
                out_ref[lane] = ctx / jnp.maximum(l_ref[...], 1e-30)

            return jnp.where(turn, 1 - qslot, qslot)

        jax.lax.fori_loop(0, count, pair, jnp.int32(0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    i32 = lambda x: jnp.asarray(x, jnp.int32).reshape(-1)
    ctx = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, R, vd), jnp.float32),
        in_specs=[smem] * 5 + [hbm] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2,) + q_rows.shape[1:], q.dtype),
                        pltpu.VMEM((2, bp, W, pt), k_pool.dtype),
                        pltpu.VMEM((2, bp, Wv, pt), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2, bp)),
                        pltpu.SemaphoreType.DMA((2, bp)),
                        pltpu.VMEM((R, W), q.dtype),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, Wv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="paged_decode_attention",
    )(i32(n), i32(pair_pages), i32(pair_lane), i32(pair_last), i32(n_pairs),
      q_rows, k_pool, v_pool)
    return ctx.reshape(B, kvh, J, vd)
