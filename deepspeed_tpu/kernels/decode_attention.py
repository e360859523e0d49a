"""Fused paged decode attention: the tier's flagship Pallas kernel.

One kernel instance per lane walks the lane's page table with
scalar-prefetch indexed block loads — QK, causal mask, online softmax,
and V-gather all happen inside the kernel, so the [C, S] score matrix
is never materialized and the paged gather (`pool[tables]` + moveaxis
in the XLA engine) disappears into the kernel's DMA schedule. int8 KV
pages are consumed DIRECTLY: the page is loaded as int8 and the
per-page scale multiplies the f32 dot-product result, so dequantization
fuses into the matmul instead of materializing a dequantized copy
(JL010's promotion rule maps exactly this taint boundary).

The XLA fallback (`_decode_attend_xla`) is a per-lane `lax.map` over a
`lax.scan` of pages sharing the LITERAL block-update helper
(`_page_update`) with the kernel body at identical shapes — that is
what makes the Pallas-interpret vs fallback parity suite a bitwise
check, not an allclose one. Math mirrors `generation._flash_attend`
(same masked online-softmax recurrence), so it is bitwise invariant to
extra fully-masked pages: serving (pool-sized tables) and `generate()`
(total-length cache) emit identical tokens per backend.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.kernels.registry import assert_probe_parity

# Largest query-chunk block one grid cell holds. Wider chunks (prefill
# buckets) are padded to a multiple of it and tiled over a grid axis so
# the [nh, C, *] score/accumulator tiles stay inside VMEM.
MAX_QUERY_BLOCK = 128


def _attn_scale(hd, dtype, quant):
    """1/sqrt(hd) in the dtype the QK product runs in: compute dtype for
    fp pages (mirrors `_flash_attend`), f32 for int8 pages (the dot runs
    in f32 and the page scale rides along with it). A host constant:
    ``hd`` is static, and Mosaic cannot legalize a bf16 ``sqrt`` traced
    into the kernel body."""
    dt = np.dtype(jnp.float32 if quant else dtype)
    return np.ones((), dt) / np.sqrt(np.asarray(hd, dt))


def _page_update(qb, kb, vb, valid, m, l, acc, scale, sk=None, sv=None):
    """ONE page of the online-softmax recurrence — shared literally by
    the Pallas kernel body and the XLA fallback so the two are bitwise
    equal by construction.

    Heads lead every operand: Mosaic only lowers batched contractions
    whose batch dimension is the leading one on both sides, and keeps
    row statistics as [.., C, 1] columns (a [nh, C] -> [nh, C, 1]
    relayout inside the kernel is not expressible).

    qb [nh, C, hd] (compute dtype); kb/vb [nh, pt, hd] (STORAGE dtype —
    fp or int8); valid [C, pt] bool (key pos <= query pos); carry
    m/l [nh, C, 1] f32, acc [nh, C, hd] f32. ``sk``/``sv`` are the page's
    per-head int8 scales [nh, 1, 1] (None for fp pages). Masked keys
    contribute exp(-1e30 - m) == 0 probability and leave the running
    max untouched — the `_flash_attend` invariance argument."""
    if sk is None:
        # fp pages: QK in compute dtype (bf16 storage casts up for free).
        # The MXU accumulator is always f32 (Mosaic rejects a bf16 one),
        # so a bf16 score is the f32 accumulation rounded once.
        s = jnp.einsum("ncd,npd->ncp", qb, kb.astype(qb.dtype),
                       preferred_element_type=jnp.float32)
        s = (s.astype(qb.dtype) * scale).astype(jnp.float32)
    else:
        # int8 pages: dot in f32, page scale FUSED after the matmul —
        # no dequantized page copy ever exists
        s = jnp.einsum("ncd,npd->ncp", qb.astype(jnp.float32),
                       kb.astype(jnp.float32)) * (sk * scale)
    s = jnp.where(valid[None, :, :], s, jnp.asarray(-1e30, jnp.float32))
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))   # [nh, C, 1]
    p = jnp.exp(s - m_new) * valid[None, :, :].astype(jnp.float32)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("ncp,npd->ncd", p, vb.astype(jnp.float32))
    if sv is not None:
        pv = pv * sv
    acc = acc * corr + pv
    return m_new, l, acc


def _finalize(l, acc, dtype):
    """Close the recurrence: acc [nh, C, hd], l [nh, C, 1] -> [nh, C, hd]
    (callers swap heads back behind the chunk axis outside the kernel)."""
    return (acc / jnp.maximum(l, 1e-30)).astype(dtype)


# -- Pallas implementation ----------------------------------------------------

def _make_kernel(mp, pt, dtype, quant):
    """Kernel body for grid (B, C-blocks, mp): lane b, query block c,
    page-table slot j (innermost, so the scratch carry runs one lane
    block's whole table). The page blocks arrive already gathered — the
    index_map reads the lane's page table out of scalar-prefetch memory,
    so the DMA engine fetches `pages[tab[b, j]]` directly (the fused
    paged V/K-gather)."""

    def body(tab_ref, qpos_ref, *refs):
        if quant:
            (q_ref, k_ref, v_ref, ks_ref, vs_ref,
             out_ref, m_ref, l_ref, acc_ref) = refs
        else:
            q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = refs
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        qb = q_ref[0]                                        # [nh, Cb, hd]
        Cb = qb.shape[1]
        hd = qb.shape[-1]
        # TPU needs >=2D iota: key positions for page-table slot j. The
        # query positions are a [Cb, 1] VMEM column (SMEM only serves
        # scalar loads), broadcast across the page's lanes.
        kpos = j * pt + jax.lax.broadcasted_iota(jnp.int32, (Cb, pt), 1)
        valid = kpos <= qpos_ref[0]                          # [Cb, pt]
        sk = ks_ref[0] if quant else None                    # [nh, 1, 1]
        sv = vs_ref[0] if quant else None
        m, l, acc = _page_update(
            qb, k_ref[0], v_ref[0], valid, m_ref[...], l_ref[...],
            acc_ref[...], _attn_scale(hd, dtype, quant), sk, sv)
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc

        @pl.when(j == mp - 1)
        def _emit():
            out_ref[0] = _finalize(l_ref[...], acc_ref[...], dtype)

    return body


def _decode_attend_pallas(q, pages_k, pages_v, tables, qpos, pt, dtype,
                          k_scale, v_scale, interpret):
    """q [B, nh, C, hd] (heads leading), qpos [B, C]; returns
    [B, nh, C, hd]."""
    B, nh, C, hd = q.shape
    mp = tables.shape[1]
    quant = k_scale is not None
    Cb = min(C, MAX_QUERY_BLOCK)
    pad = -C % Cb
    if pad:
        # pad queries sit at position 0: they attend key 0 only and are
        # sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)))
    n_cb = (C + pad) // Cb

    def lane_block(b, c, j, tab):
        return (b, 0, c, 0)

    def page_idx(b, c, j, tab):
        # THE fused paged gather: block j of lane b is physical page
        # tab[b, j], resolved from scalar-prefetch memory at DMA time
        return (tab[b, j], 0, 0, 0)

    in_specs = [
        # [B, C, 1]: a (1, Cb, 1) block is Mosaic-legal where (1, Cb) on
        # a [B, C] array is not (second-minor 1 != B)
        pl.BlockSpec((1, Cb, 1), lambda b, c, j, tab: (b, c, 0)),
        pl.BlockSpec((1, nh, Cb, hd), lane_block),
        pl.BlockSpec((1, nh, pt, hd), page_idx),
        pl.BlockSpec((1, nh, pt, hd), page_idx),
    ]
    inputs = [qpos[:, :, None], q, pages_k, pages_v]
    if quant:
        # [P, nh, 1, 1]: trailing (1, 1) equals the array dims, which a
        # (1, nh) block on [P, nh] does not satisfy
        in_specs += [pl.BlockSpec((1, nh, 1, 1), page_idx)] * 2
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_cb, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, Cb, hd), lane_block),
        scratch_shapes=[
            pltpu.VMEM((nh, Cb, 1), jnp.float32),            # running max
            pltpu.VMEM((nh, Cb, 1), jnp.float32),            # denominator
            pltpu.VMEM((nh, Cb, hd), jnp.float32),           # numerator
        ],
    )
    out = pl.pallas_call(
        _make_kernel(mp, pt, dtype, quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, C + pad, hd), dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables, *inputs)
    return out[:, :, :C] if pad else out


# -- XLA fallback / parity oracle ---------------------------------------------

def _decode_attend_xla(q, pages_k, pages_v, tables, qpos, pt, dtype,
                       k_scale, v_scale):
    """Composed-XLA twin of the kernel: `lax.map` over lanes (NOT vmap —
    per-lane execution at the kernel's exact block shapes keeps the op
    sequence, and therefore the bits, identical to one grid row) of a
    `lax.scan` over the lane's page table. Same operand layout as the
    kernel: q [B, nh, C, hd] -> [B, nh, C, hd]."""
    B, nh, C, hd = q.shape
    mp = tables.shape[1]
    quant = k_scale is not None
    scale = _attn_scale(hd, dtype, quant)

    def lane(args):
        qb, tab, qp = args                       # [nh,C,hd], [mp], [C]
        m0 = jnp.full((nh, C, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((nh, C, 1), jnp.float32)
        a0 = jnp.zeros((nh, C, hd), jnp.float32)

        def page(carry, xs):
            m, l, acc = carry
            pid, off = xs
            valid = (off + jnp.arange(pt))[None, :] <= qp[:, None]
            sk = k_scale[pid] if quant else None
            sv = v_scale[pid] if quant else None
            m, l, acc = _page_update(qb, pages_k[pid], pages_v[pid],
                                     valid, m, l, acc, scale, sk, sv)
            return (m, l, acc), None

        (_, l, acc), _ = jax.lax.scan(
            page, (m0, l0, a0), (tab, jnp.arange(mp, dtype=jnp.int32) * pt))
        return _finalize(l, acc, dtype)

    return jax.lax.map(lane, (q, tables, qpos))


# -- public entry points ------------------------------------------------------

def decode_attend(q, pages_k, pages_v, tables, qpos, *, page_tokens, dtype,
                  impl="pallas", interpret=True, k_scale=None, v_scale=None):
    """Paged fused attention: q [B, C, nh, hd] at positions qpos [B, C]
    over the page pool pages_k/v [P, nh, pt, hd] through per-lane page
    tables [B, mp]. ``impl``/``interpret`` come from the registry's
    `resolve()` and MUST be static at every jit call site (they pick the
    program). int8 pools pass ``k_scale``/``v_scale`` ([P, nh, 1, 1] or
    [P, nh] f32, per-page per-head) and the dequant fuses into the
    matmul; bf16 pools just cast at load. Returns [B, C, nh, hd]."""
    pt = int(page_tokens)
    assert pages_k.shape[2] == pt, (
        f"pool page size {pages_k.shape[2]} != page_tokens {pt}")
    tables = tables.astype(jnp.int32)
    qpos = qpos.astype(jnp.int32)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None:
        P, nh = pages_k.shape[0], pages_k.shape[1]
        k_scale = k_scale.astype(jnp.float32).reshape(P, nh, 1, 1)
        v_scale = v_scale.astype(jnp.float32).reshape(P, nh, 1, 1)
    # heads lead inside the tier (see `_page_update`); the two swaps are
    # plain XLA transposes outside the kernel
    qh = jnp.swapaxes(q, 1, 2)
    if impl == "pallas":
        out = _decode_attend_pallas(qh, pages_k, pages_v, tables, qpos, pt,
                                    dtype, k_scale, v_scale, bool(interpret))
    else:
        out = _decode_attend_xla(qh, pages_k, pages_v, tables, qpos, pt,
                                 dtype, k_scale, v_scale)
    return jnp.swapaxes(out, 1, 2)


def chunk_attend(q, cache_k, cache_v, qpos, page_tokens, dtype,
                 impl="pallas", interpret=True):
    """Contiguous-cache adapter for `generate()`-side callers: caches
    [B, nh, S, hd] (S a multiple of page_tokens) are viewed as per-lane
    page runs with an identity page table, then routed through
    `decode_attend` — so the contiguous path and the serving pool path
    run the SAME kernel and the continuous-vs-generate() oracle holds
    bitwise per backend by construction."""
    B, C, nh, hd = q.shape
    S = cache_k.shape[2]
    pt = int(page_tokens)
    assert S % pt == 0, f"cache length {S} is not a multiple of page {pt}"
    mp = S // pt

    def paged(cache):
        blocks = cache.reshape(B, nh, mp, pt, hd)
        return jnp.moveaxis(blocks, 2, 1).reshape(B * mp, nh, pt, hd)

    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    return decode_attend(q, paged(cache_k), paged(cache_v), tables, qpos,
                         page_tokens=pt, dtype=dtype, impl=impl,
                         interpret=interpret)


# -- registry probe -----------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _probe_case():
    B, C, nh, pt, hd, mp, P = 2, 2, 2, 8, 128, 2, 5
    q = (jnp.arange(B * C * nh * hd, dtype=jnp.float32)
         .reshape(B, C, nh, hd) % 7 - 3) / 11.0
    pk = (jnp.arange(P * nh * pt * hd, dtype=jnp.float32)
          .reshape(P, nh, pt, hd) % 5 - 2) / 7.0
    pv = (jnp.arange(P * nh * pt * hd, dtype=jnp.float32)
          .reshape(P, nh, pt, hd) % 9 - 4) / 13.0
    tables = jnp.asarray([[1, 3], [4, 2]], jnp.int32)
    qpos = jnp.asarray([[5, 6], [11, 12]], jnp.int32)
    return q, pk, pv, tables, qpos, pt


def probe(interpret):
    """Execution probe: a tiny paged instance through the Pallas path
    must run AND match the XLA twin. Any exception (lowering failure,
    wrong numerics) marks the kernel unavailable. Both sides run at
    full f32 matmul precision: the TPU default rounds f32 operands to
    bf16, which would drown the comparison."""
    q, pk, pv, tables, qpos, pt = _probe_case()
    with jax.default_matmul_precision("highest"):
        got = decode_attend(q, pk, pv, tables, qpos, page_tokens=pt,
                            dtype=jnp.float32, impl="pallas",
                            interpret=interpret)
        want = decode_attend(q, pk, pv, tables, qpos, page_tokens=pt,
                             dtype=jnp.float32, impl="xla")
    assert_probe_parity("decode_attention", got, want, interpret)
