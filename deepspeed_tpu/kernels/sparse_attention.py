"""Banded sink+window block-sparse attention as a Pallas kernel.

The `sparse_xla` seam computes every query with
`generation._attend_window_one`: a (SPARSE_BAND+1)-page window around
the query plus the anchor (sink) page. This module is the fused form of
that band — one kernel instance per query doing both score einsums, the
band mask, the fp32 softmax, and the PV gather in one pass. The window
SLICING stays on the XLA side (a dynamic-slice per lane, exactly like
the existing backend) — the band *math* is the kernel, so the same
entry point serves the contiguous `generate()` caches and the serving
pool's gathered windows.

The XLA fallback is a per-query `lax.map` of the LITERAL shared math
helper (`_band_math`) the kernel body runs — bitwise parity between
Pallas-interpret and the fallback by construction, and per-query
independence makes results bitwise invariant to batching/chunking
(the same argument `_chunk_attend_window` rests on).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.inference.generation import (
    _window_base,
    _window_slice_one,
)
from deepspeed_tpu.kernels.registry import assert_probe_parity


def _band_math(q, k_win, v_win, k_sink, v_sink, win_valid, sink_valid,
               dtype):
    """One query's band attention — `_attend_window_one`'s math with the
    position masks precomputed by the caller (the kernel builds them
    from 2D iota, the fallback from arange; the VALUES are identical so
    the shared body keeps the two bitwise-equal).

    The query carries a unit row axis so both contractions are batched
    matmuls with the head batch dimension LEADING on both operands — the
    only batched form Mosaic lowers (a rank-2 ``nd,nwd->nw`` has none).

    q [nh, 1, hd]; k_win/v_win [nh, W, hd]; k_sink/v_sink [nh, pt, hd];
    win_valid [1, W] bool (window key pos <= query pos); sink_valid
    [1, pt] bool (sink key pos < window base). Masked -1e30 scores
    underflow to exact-zero probability under the fp32 softmax.
    Returns [nh, 1, hd]."""
    # host constant: Mosaic cannot legalize a bf16 sqrt in the kernel body
    dt = np.dtype(dtype)
    scale = np.ones((), dt) / np.sqrt(np.asarray(q.shape[-1], dt))
    # f32 MXU accumulator (Mosaic rejects a bf16 one), rounded once to the
    # compute dtype `_attend_window_one` scores in
    f32 = dict(preferred_element_type=jnp.float32)
    s_win = jnp.einsum("nqd,nwd->nqw", q, k_win,
                       **f32).astype(dtype) * scale              # [nh,1,W]
    s_win = jnp.where(win_valid[None], s_win,
                      jnp.asarray(-1e30, s_win.dtype))
    s_sink = jnp.einsum("nqd,nsd->nqs", q, k_sink,
                        **f32).astype(dtype) * scale             # [nh,1,pt]
    s_sink = jnp.where(sink_valid[None], s_sink,
                       jnp.asarray(-1e30, s_sink.dtype))
    s = jnp.concatenate([s_sink, s_win], axis=-1).astype(jnp.float32)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dtype)
    v_all = jnp.concatenate([v_sink, v_win], axis=-2)            # [nh,pt+W,hd]
    return jnp.einsum("nqs,nsd->nqd", probs, v_all,
                      **f32).astype(dtype)                       # [nh,1,hd]


# -- Pallas implementation ----------------------------------------------------

def _make_kernel(W, pt, dtype):
    def body(pos_ref, base_ref, q_ref, kw_ref, vw_ref, ks_ref, vs_ref,
             out_ref):
        i = pl.program_id(0)
        pos = pos_ref[i]
        base = base_ref[i]
        # TPU needs >=2D iota; [1, W]/[1, pt] broadcast over heads
        kpos_w = base + jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        kpos_s = jax.lax.broadcasted_iota(jnp.int32, (1, pt), 1)
        out_ref[0] = _band_math(
            q_ref[0], kw_ref[0], vw_ref[0], ks_ref[0], vs_ref[0],
            kpos_w <= pos, kpos_s < base, dtype)

    return body


def _band_attend_pallas(q, k_win, v_win, k_sink, v_sink, pos, base, dtype,
                        interpret):
    """q [N, nh, 1, hd] -> [N, nh, 1, hd]."""
    N, nh, _, hd = q.shape
    W = k_win.shape[2]
    pt = k_sink.shape[2]

    def row(i, pos_, base_):
        return (i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, nh, 1, hd), row),
            pl.BlockSpec((1, nh, W, hd), row),
            pl.BlockSpec((1, nh, W, hd), row),
            pl.BlockSpec((1, nh, pt, hd), row),
            pl.BlockSpec((1, nh, pt, hd), row),
        ],
        out_specs=pl.BlockSpec((1, nh, 1, hd), row),
    )
    return pl.pallas_call(
        _make_kernel(W, pt, dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, nh, 1, hd), dtype),
        interpret=interpret,
        name="band_sparse_attention",
    )(pos, base, q, k_win, v_win, k_sink, v_sink)


# -- XLA fallback / parity oracle ---------------------------------------------

def _band_attend_xla(q, k_win, v_win, k_sink, v_sink, pos, base, dtype):
    """Per-query `lax.map` of the shared band math at the kernel's exact
    block shapes (NOT vmap: unbatched per-query execution keeps the op
    sequence, and therefore the bits, identical to one grid cell)."""
    W = k_win.shape[2]
    pt = k_sink.shape[2]

    def one(args):
        qi, kw, vw, ks, vs, p, b = args
        win_valid = ((b + jnp.arange(W)) <= p)[None, :]
        sink_valid = (jnp.arange(pt) < b)[None, :]
        return _band_math(qi, kw, vw, ks, vs, win_valid, sink_valid, dtype)

    return jax.lax.map(one, (q, k_win, v_win, k_sink, v_sink, pos, base))


# -- public entry points ------------------------------------------------------

def band_attend(q, k_win, v_win, k_sink, v_sink, pos, base, *, dtype,
                impl="pallas", interpret=True):
    """Banded sink+window attention for N independent queries: q
    [N, nh, hd] against window slices k_win/v_win [N, nh, W, hd]
    (tokens [base, base+W) per query) plus the anchor page k_sink/v_sink
    [N, nh, pt, hd] (tokens [0, pt)). ``pos``/``base`` are [N] int32.
    ``impl``/``interpret`` come from the registry's `resolve()` and must
    be static at every jit call site. Returns [N, nh, hd]."""
    pos = pos.astype(jnp.int32)
    base = base.astype(jnp.int32)
    qr = q[:, :, None, :]                # unit row axis, see `_band_math`
    if impl == "pallas":
        out = _band_attend_pallas(qr, k_win, v_win, k_sink, v_sink, pos,
                                  base, dtype, bool(interpret))
    else:
        out = _band_attend_xla(qr, k_win, v_win, k_sink, v_sink, pos, base,
                               dtype)
    return out[:, :, 0]


def _band_block(qb, pb, cache_k, cache_v, pt, dtype, impl, interpret):
    """One block of queries through the band: qb [B, c, nh, hd] at
    positions pb [B, c] against per-lane caches [B, nh, S, hd]. Window
    slicing is plain XLA (vmapped dynamic-slice, same as the sparse_xla
    seam); the flattened [B*c] queries then run the band kernel."""
    B, c, nh, hd = qb.shape
    base = _window_base(pb, pt)                                  # [B, c]

    def slices(ck, cv, brow):
        return jax.vmap(
            lambda b: _window_slice_one(ck, cv, b, pt))(brow)

    kw, vw, ks, vs = jax.vmap(slices)(cache_k, cache_v, base)
    flat = lambda x: x.reshape((B * c,) + x.shape[2:])
    ctx = band_attend(flat(qb), flat(kw), flat(vw), flat(ks), flat(vs),
                      pb.reshape(B * c), base.reshape(B * c),
                      dtype=dtype, impl=impl, interpret=interpret)
    return ctx.reshape(B, c, nh, hd)


def chunk_band_attend(q, cache_k, cache_v, qpos, page_tokens, dtype,
                      impl="pallas", interpret=True):
    """Whole-chunk band attention: q [B, C, nh, hd] at positions qpos
    [B, C] over the already-written caches [B, nh, S, hd]. When C is a
    multiple of the page size, queries run pt at a time under a lax.scan
    (bounding the materialized window slices to one block — the
    `_chunk_attend_window` memory argument); otherwise (the k+1
    speculative verify chunk) the whole chunk flattens at once. Each
    query slices its OWN canonical window either way, so the per-query
    math is bit-identical to the decode step's regardless of chunking."""
    B, C, nh, hd = q.shape
    pt = int(page_tokens)
    if C % pt == 0 and C > pt:
        nb = C // pt
        q_b = jnp.moveaxis(q.reshape(B, nb, pt, nh, hd), 1, 0)
        p_b = jnp.moveaxis(qpos.reshape(B, nb, pt), 1, 0)

        def block(_, xs):
            qb, pb = xs
            return None, _band_block(qb, pb, cache_k, cache_v, pt, dtype,
                                     impl, interpret)

        _, ctx_b = jax.lax.scan(block, None, (q_b, p_b))
        return jnp.moveaxis(ctx_b, 0, 1).reshape(B, C, nh, hd)
    return _band_block(q, qpos, cache_k, cache_v, pt, dtype, impl,
                       interpret)


# -- registry probe -----------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _probe_case():
    N, nh, pt, hd = 2, 2, 8, 128
    W = 2 * pt
    q = (jnp.arange(N * nh * hd, dtype=jnp.float32)
         .reshape(N, nh, hd) % 7 - 3) / 11.0
    kw = (jnp.arange(N * nh * W * hd, dtype=jnp.float32)
          .reshape(N, nh, W, hd) % 5 - 2) / 7.0
    vw = (jnp.arange(N * nh * W * hd, dtype=jnp.float32)
          .reshape(N, nh, W, hd) % 9 - 4) / 13.0
    ks = kw[:, :, :pt] * 0.5
    vs = vw[:, :, :pt] * 0.25
    pos = jnp.asarray([19, 26], jnp.int32)
    base = jnp.asarray([8, 16], jnp.int32)
    return q, kw, vw, ks, vs, pos, base


def probe(interpret):
    """Execution probe: a tiny band instance through the Pallas path
    must run AND match the XLA twin (at full f32 matmul precision, see
    ``decode_attention.probe``)."""
    q, kw, vw, ks, vs, pos, base = _probe_case()
    with jax.default_matmul_precision("highest"):
        got = band_attend(q, kw, vw, ks, vs, pos, base, dtype=jnp.float32,
                          impl="pallas", interpret=interpret)
        want = band_attend(q, kw, vw, ks, vs, pos, base, dtype=jnp.float32,
                           impl="xla")
    assert_probe_parity("sparse_attention", got, want, interpret)
