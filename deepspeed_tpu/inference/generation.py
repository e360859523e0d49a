"""KV-cache autoregressive decoding over the training stack's params.

Beyond the v0.3.10 reference (DeepSpeed-Inference came later). TPU-first
design: prefill is ONE single-pass causal forward over the whole prompt
(``_forward_full`` — every K/V computed in one batched call, so the
compiler sees whole-sequence GEMMs instead of S sequential batch-1
matmuls), and decode is ONE jitted ``lax.scan`` over positions — no
per-token host round-trips — with an inner ``lax.scan`` over the
scan-stacked layer params (the same [L, ...] stacking the training path
uses, so a trained checkpoint drops in unchanged). Static shapes
throughout: the KV cache is [L, B, nh, S_max, hd] and future positions
are masked, so XLA compiles one program for any prompt/continuation
split.

The per-layer math mirrors ``DeepSpeedTransformerLayer`` (pre-LN:
x + attn(LN(x)), x + ffn(LN(x)), fused qkv GEMM) — asserted equal to the
full forward in ``tests/unit/test_generation.py``.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.quantization import (
    embed_rows,
    logits_table,
    maybe_dequant,
    vocab_size,
)

# Pluggable attention backends. "dense" is the exact causal forward and
# the parity oracle; "flash" computes the SAME math blockwise with an
# online softmax (allclose to dense, bitwise-stable against cache-length
# changes); "sparse_xla" is the banded block-sparse composition from
# ops/sparse_attention (per-query window of SPARSE_BAND+1 pages plus the
# global anchor page 0 — the layout tests/perf/longseq_bench.py measures
# at 65x dense for seq 16384). "pallas_decode"/"pallas_sparse" route the
# flash and banded math through the hand-fused kernel tier
# (deepspeed_tpu/kernels/): same shapes and masks, Pallas bodies, with
# the registry picking Pallas vs the composed-XLA fallback at resolve
# time (the kernel_impl/kernel_interpret statics).
ATTENTION_IMPLS = ("dense", "flash", "sparse_xla",
                   "pallas_decode", "pallas_sparse")

# The backends that resolve through the kernel registry.
KERNEL_ATTENTION_IMPLS = ("pallas_decode", "pallas_sparse")

# Page granularity shared by the sparse window, the flash key blocks,
# and the serving KV pool's pages (kv_pool.py) — one constant so a
# sparse window is always a whole number of pool pages.
DEFAULT_PAGE_TOKENS = 128

# Banded width of the sparse window in pages: a query attends its own
# page, SPARSE_BAND pages below it, and the anchor page 0.
SPARSE_BAND = 1


def _round_up(n, m):
    return -(-int(n) // int(m)) * int(m)


def resolve_page_tokens(page_tokens, max_seq_len):
    """The EFFECTIVE page size for a given cache length: never larger
    than the cache, and always dividing it (falling back to the gcd), so
    a lane is a whole number of pages and a paged gather reassembles the
    exact contiguous layout."""
    pt = min(int(page_tokens or DEFAULT_PAGE_TOKENS), int(max_seq_len))
    if max_seq_len % pt:
        pt = math.gcd(pt, int(max_seq_len))
    return max(pt, 1)


def _layer_tree(params):
    """The stacked per-layer param tree and the names of its blocks.

    The scan body (models/gpt2.py ``_ScannedDecoderLayer``) holds ONE child
    module (the fused layer); its params sit one level below ``layers``."""
    layers = params["params"]["transformer"]["layers"]
    children = list(layers.values())
    assert len(children) == 1, f"expected one scanned child, got {list(layers)}"
    return children[0]


def _ln(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _cache_dtype(params):
    """The dtype ``_step``/``_forward_chunk`` actually produce (and so
    the dtype KV caches must carry): int8-quantized tables dequantize to
    f32; otherwise the embedding dtype flows through the residual stream,
    so a bf16 checkpoint decodes (and caches) in bf16. Hardcoding f32
    would make the cache/carry dtypes disagree with bf16 k/v slices and
    logits and crash at trace time."""
    tr = params["params"]["transformer"]
    emb_dtype = (jnp.float32 if "kernel_q" in tr["wte"]
                 else tr["wte"]["embedding"].dtype)
    return jnp.result_type(emb_dtype, tr["wpe"]["embedding"].dtype)


def _decode_one(layer_p, h, cache_k, cache_v, pos, nh):
    """One token through one layer against the cache.

    h [B, H]; cache_k/v [B, nh, S_max, hd]; pos scalar. Returns updated
    (h, cache_k, cache_v)."""
    B, H = h.shape
    hd = H // nh

    a_in = _ln(h, layer_p["ln_attn"])
    qkv = a_in @ maybe_dequant(layer_p["qkv"]) + layer_p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, nh, hd)
    k = k.reshape(B, nh, hd)
    v = v.reshape(B, nh, hd)

    cache_k = jax.lax.dynamic_update_index_in_dim(cache_k, k, pos, axis=2)
    cache_v = jax.lax.dynamic_update_index_in_dim(cache_v, v, pos, axis=2)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, h.dtype))
    scores = jnp.einsum("bnd,bnsd->bns", q, cache_k) * scale     # [B,nh,S]
    S_max = cache_k.shape[2]
    valid = jnp.arange(S_max) <= pos
    scores = jnp.where(valid[None, None, :], scores,
                       jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(h.dtype)
    ctx = jnp.einsum("bns,bnsd->bnd", probs, cache_v).reshape(B, H)
    a = ctx @ maybe_dequant(layer_p["attn_out"]) + layer_p["attn_out"]["bias"]
    h = h + a

    f_in = _ln(h, layer_p["ln_ffn"])
    f = f_in @ maybe_dequant(layer_p["ff1"]) + layer_p["ff1"]["bias"]
    f = jax.nn.gelu(f, approximate=False)
    f = f @ maybe_dequant(layer_p["ff2"]) + layer_p["ff2"]["bias"]
    return h + f, cache_k, cache_v


def _step(params, nh, caches, token, pos):
    """Embed one token, run the layer stack against the caches, return
    (next-token logits [B, V], updated caches)."""
    tr = params["params"]["transformer"]
    wpe = tr["wpe"]["embedding"]
    layer_p = _layer_tree(params)

    h = embed_rows(tr["wte"], token) + wpe[pos]                  # [B, H]

    # scan over the stacked layer dim with per-layer cache slices as
    # scanned inputs — mirrors the training stack's nn.scan
    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        h, ck_l, cv_l = _decode_one(lp, h, ck_l, cv_l, pos, nh)
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))

    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return logits, caches


# -- pluggable attention backends --------------------------------------------
#
# The sparse backend's ONE attention primitive: every sparse path
# (full prefill, chunked prefill, speculative verify, decode — in both
# generate() and the serving engine) computes each query with
# `_attend_window_one` at identical shapes, so the continuous-batching
# greedy oracle holds bitwise per backend by construction instead of by
# numerical accident.

def _window_base(pos, pt):
    """First token of a query's canonical sparse window: SPARSE_BAND
    pages below the query's own page, clamped at 0."""
    return jnp.maximum(pos // pt - SPARSE_BAND, 0) * pt


def _window_slice_one(cache_k, cache_v, base, pt):
    """One lane's window slice: cache [nh, S, hd] -> window pair
    [nh, (SPARSE_BAND+1)*pt, hd] starting at token ``base`` plus the
    anchor page pair [nh, pt, hd] (tokens [0, pt))."""
    W = (SPARSE_BAND + 1) * pt
    k_win = jax.lax.dynamic_slice_in_dim(cache_k, base, W, axis=1)
    v_win = jax.lax.dynamic_slice_in_dim(cache_v, base, W, axis=1)
    return k_win, v_win, cache_k[:, :pt], cache_v[:, :pt]


def _attend_window_one(q, k_win, v_win, k_sink, v_sink, pos, base, dtype):
    """One query's banded block-sparse attention: q [nh, hd] against its
    window slice ([nh, W, hd], tokens [base, base+W)) plus the anchor
    page ([nh, pt, hd], tokens [0, pt) — the global block the longseq
    bench's sparse_xla layout keeps). Window keys are valid iff their
    token index <= pos; anchor keys iff strictly below ``base`` (when
    base == 0 the window already covers them, so nothing double-counts).
    Masked -1e30 scores underflow to exact-zero probability under the
    fp32 softmax — the same exact-zero argument the dense oracle rests
    on. For pos < (SPARSE_BAND+1)*pt the window covers every cached
    token, so short sequences are exactly full attention."""
    hd = q.shape[-1]
    W = k_win.shape[1]
    pt = k_sink.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype))
    s_win = jnp.einsum("nd,nwd->nw", q, k_win) * scale           # [nh, W]
    kpos_w = base + jnp.arange(W)
    s_win = jnp.where((kpos_w <= pos)[None, :], s_win,
                      jnp.asarray(-1e30, s_win.dtype))
    s_sink = jnp.einsum("nd,nsd->ns", q, k_sink) * scale         # [nh, pt]
    s_sink = jnp.where((jnp.arange(pt) < base)[None, :], s_sink,
                       jnp.asarray(-1e30, s_sink.dtype))
    s = jnp.concatenate([s_sink, s_win], axis=-1)                # [nh, pt+W]
    probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
    v_all = jnp.concatenate([v_sink, v_win], axis=-2)
    return jnp.einsum("ns,nsd->nd", probs, v_all)                # [nh, hd]


def _window_qkv(layer_p, h, nh):
    """The decode step's fused qkv projection for one token per lane —
    the head of `_decode_one`, shared with the sparse window programs
    (here and in the serving engine's paged decode)."""
    B, H = h.shape
    hd = H // nh
    a_in = _ln(h, layer_p["ln_attn"])
    qkv = a_in @ maybe_dequant(layer_p["qkv"]) + layer_p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return q.reshape(B, nh, hd), k.reshape(B, nh, hd), v.reshape(B, nh, hd)


def _window_finish(layer_p, h, ctx):
    """attn_out projection + residual + FFN — the tail of `_decode_one`,
    shared with the serving engine's paged window decode."""
    B, H = h.shape
    a = (ctx.reshape(B, H) @ maybe_dequant(layer_p["attn_out"])
         + layer_p["attn_out"]["bias"])
    h = h + a
    f_in = _ln(h, layer_p["ln_ffn"])
    f = f_in @ maybe_dequant(layer_p["ff1"]) + layer_p["ff1"]["bias"]
    f = jax.nn.gelu(f, approximate=False)
    f = f @ maybe_dequant(layer_p["ff2"]) + layer_p["ff2"]["bias"]
    return h + f


def _decode_one_window(layer_p, h, cache_k, cache_v, pos, nh, pt):
    """One token through one layer with banded-sparse attention: the
    same qkv/residual/FFN math as `_decode_one`, but each lane attends
    only its canonical window plus the anchor page — O(pt) keys per
    token instead of O(S)."""
    q, k, v = _window_qkv(layer_p, h, nh)
    cache_k = jax.lax.dynamic_update_index_in_dim(cache_k, k, pos, axis=2)
    cache_v = jax.lax.dynamic_update_index_in_dim(cache_v, v, pos, axis=2)
    base = _window_base(pos, pt)

    def lane(qi, ck, cv):
        k_win, v_win, k_sink, v_sink = _window_slice_one(ck, cv, base, pt)
        return _attend_window_one(qi, k_win, v_win, k_sink, v_sink,
                                  pos, base, h.dtype)

    ctx = jax.vmap(lane)(q, cache_k, cache_v)                    # [B, nh, hd]
    return _window_finish(layer_p, h, ctx), cache_k, cache_v


def _step_window(params, nh, caches, token, pos, pt):
    """`_step` with the sparse backend's windowed per-token attention."""
    tr = params["params"]["transformer"]
    wpe = tr["wpe"]["embedding"]
    layer_p = _layer_tree(params)
    h = embed_rows(tr["wte"], token) + wpe[pos]

    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        h, ck_l, cv_l = _decode_one_window(lp, h, ck_l, cv_l, pos, nh, pt)
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return logits, caches


def _decode_one_kernel(layer_p, h, cache_k, cache_v, pos, nh, pt,
                       kernel_impl, kernel_interpret):
    """One token through one layer with the fused decode-attention
    kernel: `_decode_one`'s qkv/write/residual/FFN around the kernel
    tier's paged online-softmax attention at C=1. Requires the cache
    length to be a multiple of ``pt``."""
    from deepspeed_tpu import kernels  # lazy: kernels imports this module
    q, k, v = _window_qkv(layer_p, h, nh)
    cache_k = jax.lax.dynamic_update_index_in_dim(cache_k, k, pos, axis=2)
    cache_v = jax.lax.dynamic_update_index_in_dim(cache_v, v, pos, axis=2)
    B = h.shape[0]
    qpos = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    ctx = kernels.chunk_attend(q[:, None], cache_k, cache_v, qpos, pt,
                               h.dtype, impl=kernel_impl or "xla",
                               interpret=bool(kernel_interpret))[:, 0]
    return _window_finish(layer_p, h, ctx), cache_k, cache_v


def _step_kernel(params, nh, caches, token, pos, pt, kernel_impl,
                 kernel_interpret):
    """`_step` with the fused decode-attention kernel per layer."""
    tr = params["params"]["transformer"]
    wpe = tr["wpe"]["embedding"]
    layer_p = _layer_tree(params)
    h = embed_rows(tr["wte"], token) + wpe[pos]

    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        h, ck_l, cv_l = _decode_one_kernel(lp, h, ck_l, cv_l, pos, nh, pt,
                                           kernel_impl, kernel_interpret)
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return logits, caches


def _decode_one_window_kernel(layer_p, h, cache_k, cache_v, pos, nh, pt,
                              kernel_impl, kernel_interpret):
    """`_decode_one_window` with the band math in the kernel tier: the
    window slicing stays the same XLA dynamic-slice; the fused band
    kernel does both score einsums, the mask, and the softmax."""
    from deepspeed_tpu import kernels  # lazy: kernels imports this module
    q, k, v = _window_qkv(layer_p, h, nh)
    cache_k = jax.lax.dynamic_update_index_in_dim(cache_k, k, pos, axis=2)
    cache_v = jax.lax.dynamic_update_index_in_dim(cache_v, v, pos, axis=2)
    base = _window_base(pos, pt)
    kw, vw, ks, vs = jax.vmap(
        lambda ck, cv: _window_slice_one(ck, cv, base, pt))(cache_k, cache_v)
    B = h.shape[0]
    ctx = kernels.band_attend(
        q, kw, vw, ks, vs, jnp.broadcast_to(pos, (B,)),
        jnp.broadcast_to(base, (B,)), dtype=h.dtype,
        impl=kernel_impl or "xla", interpret=bool(kernel_interpret))
    return _window_finish(layer_p, h, ctx), cache_k, cache_v


def _step_window_kernel(params, nh, caches, token, pos, pt, kernel_impl,
                        kernel_interpret):
    """`_step_window` with the banded-sparse attention fused in the
    kernel tier."""
    tr = params["params"]["transformer"]
    wpe = tr["wpe"]["embedding"]
    layer_p = _layer_tree(params)
    h = embed_rows(tr["wte"], token) + wpe[pos]

    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        h, ck_l, cv_l = _decode_one_window_kernel(
            lp, h, ck_l, cv_l, pos, nh, pt, kernel_impl, kernel_interpret)
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return logits, caches


def _chunk_attend_window(q, cache_k, cache_v, qpos, pt, dtype):
    """Canonical windowed attention for a whole chunk of queries: q
    [B, C, nh, hd] at per-query positions ``qpos`` [B, C] against the
    already-written caches [B, nh, S, hd]. C must be a multiple of
    ``pt``. Queries run in blocks of pt under a lax.scan (bounding the
    materialized window slices to one block), but each query slices its
    OWN canonical window — so the per-query math is bit-identical to the
    decode step's no matter how callers chunk, bucket, or pad the
    sequence."""
    B, C, nh, hd = q.shape
    assert C % pt == 0, f"chunk width {C} is not a multiple of page {pt}"
    nb = C // pt

    def one(qi, p, ck, cv):
        base = _window_base(p, pt)
        k_win, v_win, k_sink, v_sink = _window_slice_one(ck, cv, base, pt)
        return _attend_window_one(qi, k_win, v_win, k_sink, v_sink,
                                  p, base, dtype)

    q_b = jnp.moveaxis(q.reshape(B, nb, pt, nh, hd), 1, 0)       # [nb,B,pt,..]
    p_b = jnp.moveaxis(qpos.reshape(B, nb, pt), 1, 0)            # [nb,B,pt]

    def block(_, xs):
        qb, pb = xs
        ctx = jax.vmap(                                          # over lanes
            lambda qrow, prow, ck, cv: jax.vmap(                 # over queries
                lambda qi, p: one(qi, p, ck, cv))(qrow, prow))(
            qb, pb, cache_k, cache_v)
        return None, ctx                                         # [B,pt,nh,hd]

    _, ctx_b = jax.lax.scan(block, None, (q_b, p_b))
    return jnp.moveaxis(ctx_b, 0, 1).reshape(B, C, nh, hd)


def _flash_attend(q, cache_k, cache_v, qpos, pt, dtype):
    """Blockwise online-softmax causal attention (the flash recipe): q
    [B, C, nh, hd] at positions ``qpos`` [B, C] over caches
    [B, nh, S, hd] with S a multiple of ``pt``. Never materializes the
    [C, S] score matrix; accumulates a running (max, denominator,
    numerator) triple in fp32 across key blocks. Math-equal to dense
    (allclose — the fp summation order differs) and BITWISE invariant to
    extra fully-masked key blocks: a masked block contributes zero
    probability, leaves the running max unchanged, and scales the
    accumulators by exp(0) == 1 — so serving (S_max-long cache) and
    generate() (total-length cache) emit identical tokens."""
    B, C, nh, hd = q.shape
    S = cache_k.shape[2]
    assert S % pt == 0, f"cache length {S} is not a multiple of page {pt}"
    nbc = S // pt
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype))
    k_b = jnp.moveaxis(cache_k.reshape(B, nh, nbc, pt, hd), 2, 0)
    v_b = jnp.moveaxis(cache_v.reshape(B, nh, nbc, pt, hd), 2, 0)
    koff = jnp.arange(nbc) * pt

    m0 = jnp.full((B, nh, C), -1e30, jnp.float32)
    l0 = jnp.zeros((B, nh, C), jnp.float32)
    a0 = jnp.zeros((B, nh, C, hd), jnp.float32)

    def block(carry, xs):
        m, l, acc = carry
        kb, vb, off = xs
        s = jnp.einsum("bqnd,bnsd->bnqs", q, kb) * scale         # [B,nh,C,pt]
        valid = ((off + jnp.arange(pt))[None, None, None, :]
                 <= qpos[:, None, :, None])
        s = jnp.where(valid, s.astype(jnp.float32),
                      jnp.asarray(-1e30, jnp.float32))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * valid                # masked -> 0
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnqs,bnsd->bnqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    (_, l, acc), _ = jax.lax.scan(block, (m0, l0, a0), (k_b, v_b, koff))
    ctx = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    return jnp.moveaxis(ctx, 2, 1)                               # [B,C,nh,hd]


def filter_logits(logits, top_k=0, top_p=1.0):
    """Standard sampling controls, jit-traceable with TRACED knobs (no
    recompile per value): keep the top_k highest logits (0 = disabled),
    THEN the smallest set whose renormalized probabilities reach top_p
    (1.0 = disabled) — the sequential HF warper semantics, so top_p mass
    is computed over the top_k survivors. Everything else goes to -inf.

    Call AFTER temperature scaling (nucleus mass is defined on the
    distribution actually sampled), as the decode path does."""
    logits = logits.astype(jnp.float32)
    NEG = jnp.asarray(-1e30, jnp.float32)
    V = logits.shape[-1]

    order = jnp.argsort(-logits, axis=-1)                  # desc
    ranks = jnp.argsort(order, axis=-1)                    # rank of each id

    # top-k: rank must be < k (k<=0 disables)
    k = jnp.where(top_k > 0, top_k, V)
    logits = jnp.where(ranks < k, logits, NEG)

    # top-p over the top_k SURVIVORS (softmax renormalizes over them):
    # keep ids whose exclusive cumulative prob is < top_p — the best
    # token always survives; top_p >= 1 is an exact no-op (fp32 cumsum
    # error over a big vocab could otherwise mask tail tokens)
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs               # exclusive
    keep_sorted = (cum < top_p) | (top_p >= 1.0)
    keep = jnp.take_along_axis(keep_sorted, ranks, axis=-1)

    return jnp.where(keep, logits, NEG)


def _prefill(params, prompt_ids, n_layers, n_heads, head_dim, total):
    """Token-by-token scan prefill — the PARITY REFERENCE.

    Allocates the KV caches for ``total`` positions and scans the prompt
    through them one position at a time (same step as decode). No live
    path uses this anymore: ``generate()``/``beam_search()``/serving all
    prefill through the single-pass ``_forward_full``, and the tests pin
    that path bitwise (greedy tokens) / allclose (KV) against this one."""
    B, S = prompt_ids.shape
    tr = params["params"]["transformer"]
    dtype = _cache_dtype(params)
    shape = (n_layers, B, n_heads, total, head_dim)
    caches = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def prefill_body(carry, pos):
        caches, _ = carry
        logits, caches = _step(params, n_heads, caches, prompt_ids[:, pos], pos)
        return (caches, logits), None

    V = vocab_size(tr["wte"])
    (caches, last_logits), _ = jax.lax.scan(
        prefill_body, (caches, jnp.zeros((B, V), dtype)), jnp.arange(S))
    return caches, last_logits


def _chunk_layer(layer_p, h, cache_k, cache_v, starts, nh):
    """A whole chunk of positions through one layer against the cache.

    h [B, C, H]; cache_k/v [B, nh, S_cache, hd]; starts [B] is each
    lane's first position (0 for plain prefill, the chunk/prefix offset
    otherwise). The chunk's K/V are written into the cache FIRST, then
    every query attends over the full cache under the same
    ``arange(S) <= pos`` mask the decode step uses — cached positions
    before ``starts`` (earlier chunks, prefix-cache hits) are visible,
    later positions mask to exact-zero probability."""
    B, C, H = h.shape
    hd = H // nh

    a_in = _ln(h, layer_p["ln_attn"])
    qkv = a_in @ maybe_dequant(layer_p["qkv"]) + layer_p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, C, nh, hd)
    k = jnp.moveaxis(k.reshape(B, C, nh, hd), 1, 2)          # [B, nh, C, hd]
    v = jnp.moveaxis(v.reshape(B, C, nh, hd), 1, 2)

    def put(cache, new, s):
        # per-position scatter, NOT dynamic_update_slice: when a lane's
        # bucket pad runs past the cache end (large start + padded chunk)
        # the OOB pad writes must be DROPPED — a slice update would clamp
        # the start and shift real KV onto wrong positions
        return cache.at[:, s + jnp.arange(C), :].set(new, mode="drop")

    cache_k = jax.vmap(put)(cache_k, k, starts)
    cache_v = jax.vmap(put)(cache_v, v, starts)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, h.dtype))
    scores = jnp.einsum("bqnd,bnsd->bnqs", q, cache_k) * scale  # [B,nh,C,S]
    S_cache = cache_k.shape[2]
    pos = starts[:, None] + jnp.arange(C)[None, :]              # [B, C]
    valid = jnp.arange(S_cache)[None, None, :] <= pos[:, :, None]
    scores = jnp.where(valid[:, None, :, :], scores,
                       jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(h.dtype)
    ctx = jnp.einsum("bnqs,bnsd->bqnd", probs, cache_v).reshape(B, C, H)
    a = ctx @ maybe_dequant(layer_p["attn_out"]) + layer_p["attn_out"]["bias"]
    h = h + a

    f_in = _ln(h, layer_p["ln_ffn"])
    f = f_in @ maybe_dequant(layer_p["ff1"]) + layer_p["ff1"]["bias"]
    f = jax.nn.gelu(f, approximate=False)
    f = f @ maybe_dequant(layer_p["ff2"]) + layer_p["ff2"]["bias"]
    return h + f, cache_k, cache_v


def _chunk_layer_with(layer_p, h, cache_k, cache_v, starts, nh, attend):
    """`_chunk_layer`'s qkv/write/residual/FFN shell around a pluggable
    ``attend(q, cache_k, cache_v, qpos)`` (window or flash). The dense
    path stays in `_chunk_layer` untouched — it is the bitwise parity
    oracle and must not move."""
    B, C, H = h.shape
    hd = H // nh

    a_in = _ln(h, layer_p["ln_attn"])
    qkv = a_in @ maybe_dequant(layer_p["qkv"]) + layer_p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, C, nh, hd)
    k = jnp.moveaxis(k.reshape(B, C, nh, hd), 1, 2)          # [B, nh, C, hd]
    v = jnp.moveaxis(v.reshape(B, C, nh, hd), 1, 2)

    def put(cache, new, s):
        return cache.at[:, s + jnp.arange(C), :].set(new, mode="drop")

    cache_k = jax.vmap(put)(cache_k, k, starts)
    cache_v = jax.vmap(put)(cache_v, v, starts)

    qpos = starts[:, None] + jnp.arange(C)[None, :]              # [B, C]
    ctx = attend(q, cache_k, cache_v, qpos).reshape(B, C, H)
    a = ctx @ maybe_dequant(layer_p["attn_out"]) + layer_p["attn_out"]["bias"]
    h = h + a

    f_in = _ln(h, layer_p["ln_ffn"])
    f = f_in @ maybe_dequant(layer_p["ff1"]) + layer_p["ff1"]["bias"]
    f = jax.nn.gelu(f, approximate=False)
    f = f @ maybe_dequant(layer_p["ff2"]) + layer_p["ff2"]["bias"]
    return h + f, cache_k, cache_v


def _chunk_layer_window(layer_p, h, cache_k, cache_v, starts, nh, pt):
    """`_chunk_layer` with the banded-sparse backend: identical qkv
    projection and cache writes, then every query attends only its
    canonical window + anchor — O(C·pt) attention per layer instead of
    O(C·S). Requires the chunk width to be a multiple of ``pt``
    (callers pad)."""
    return _chunk_layer_with(
        layer_p, h, cache_k, cache_v, starts, nh,
        lambda q, ck, cv, qpos: _chunk_attend_window(q, ck, cv, qpos, pt,
                                                     h.dtype))


def _chunk_layer_flash(layer_p, h, cache_k, cache_v, starts, nh, pt):
    """`_chunk_layer` with the flash backend: identical qkv projection
    and cache writes, attention via the blockwise online softmax —
    no [C, S] score matrix is ever materialized. Requires the cache
    length to be a multiple of ``pt`` (callers allocate so)."""
    return _chunk_layer_with(
        layer_p, h, cache_k, cache_v, starts, nh,
        lambda q, ck, cv, qpos: _flash_attend(q, ck, cv, qpos, pt, h.dtype))


def _chunk_layer_kernel(layer_p, h, cache_k, cache_v, starts, nh, pt,
                        kernel_impl, kernel_interpret):
    """`_chunk_layer` with the fused decode-attention kernel: identical
    qkv projection and cache writes, attention through the kernel tier's
    paged online-softmax body (kernels.chunk_attend views the contiguous
    cache as identity-mapped pages, so this is the same program the
    serving pool runs). Requires the cache length to be a multiple of
    ``pt`` (callers allocate so)."""
    from deepspeed_tpu import kernels  # lazy: kernels imports this module
    return _chunk_layer_with(
        layer_p, h, cache_k, cache_v, starts, nh,
        lambda q, ck, cv, qpos: kernels.chunk_attend(
            q, ck, cv, qpos, pt, h.dtype,
            impl=kernel_impl or "xla", interpret=bool(kernel_interpret)))


def _chunk_layer_kernel_window(layer_p, h, cache_k, cache_v, starts, nh, pt,
                               kernel_impl, kernel_interpret):
    """`_chunk_layer` with the banded block-sparse kernel: the window
    slicing stays XLA (same canonical per-query window as sparse_xla);
    the band math runs in the kernel tier. Requires the chunk width to
    be a multiple of ``pt`` OR the small k+1 verify chunk (kernels
    .chunk_band_attend handles both)."""
    from deepspeed_tpu import kernels  # lazy: kernels imports this module
    return _chunk_layer_with(
        layer_p, h, cache_k, cache_v, starts, nh,
        lambda q, ck, cv, qpos: kernels.chunk_band_attend(
            q, ck, cv, qpos, pt, h.dtype,
            impl=kernel_impl or "xla", interpret=bool(kernel_interpret)))


def _forward_chunk(params, n_heads, caches, ids, starts, attn_impl="dense",
                   page_tokens=DEFAULT_PAGE_TOKENS, kernel_impl=None,
                   kernel_interpret=False):
    """Single-pass causal forward of ``ids`` [B, C] written into
    ``caches`` ([L, B, nh, S_cache, hd]) at per-lane offsets ``starts``
    [B]. Returns (hidden states [B, C, H] BEFORE the final LN, updated
    caches). The shared core under full-sequence prefill, chunked
    prefill, and prefix-cache-seeded prefill: ``starts`` and the cache
    contents are traced operands, so one compiled program per (B, C,
    S_cache) covers all of them. ``attn_impl``/``page_tokens`` are
    static: they pick the per-layer attention program (dense stays the
    default and is byte-for-byte the original path).
    ``kernel_impl``/``kernel_interpret`` are the registry-resolved
    statics for the pallas_* backends (None -> the XLA fallback)."""
    tr = params["params"]["transformer"]
    layer_p = _layer_tree(params)
    C = ids.shape[1]
    pos = starts[:, None] + jnp.arange(C)[None, :]               # [B, C]
    h = embed_rows(tr["wte"], ids) + tr["wpe"]["embedding"][pos]

    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        if attn_impl == "sparse_xla":
            h, ck_l, cv_l = _chunk_layer_window(lp, h, ck_l, cv_l, starts,
                                                n_heads, page_tokens)
        elif attn_impl == "flash":
            h, ck_l, cv_l = _chunk_layer_flash(lp, h, ck_l, cv_l, starts,
                                               n_heads, page_tokens)
        elif attn_impl == "pallas_decode":
            h, ck_l, cv_l = _chunk_layer_kernel(
                lp, h, ck_l, cv_l, starts, n_heads, page_tokens,
                kernel_impl, kernel_interpret)
        elif attn_impl == "pallas_sparse":
            h, ck_l, cv_l = _chunk_layer_kernel_window(
                lp, h, ck_l, cv_l, starts, n_heads, page_tokens,
                kernel_impl, kernel_interpret)
        else:
            h, ck_l, cv_l = _chunk_layer(lp, h, ck_l, cv_l, starts, n_heads)
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))
    return h, caches


def _ngram_draft(history, pos, k):
    """Self-drafting proposal: ``k`` draft tokens from a bigram
    (prompt-lookup) match over one lane's own token history — no second
    model, so the drafter is free relative to a forward pass.

    ``history`` [S] holds the lane's tokens by position (prompt, then
    every emitted token); ``history[pos]`` is the PENDING token about to
    be fed at position ``pos``. The drafter finds the LATEST earlier
    occurrence of the bigram ``(history[pos-1], history[pos])`` and
    proposes the tokens that followed it, CYCLING the matched stretch
    once it runs out instead of reading past ``pos``: entries above the
    pending position hold junk from rejected speculation, and the latest
    match of a loopy sequence sits right below ``pos``, so a straight
    gather would draft garbage from position 2 onward — the periodic
    extension instead turns a period-p greedy loop into k exact drafts.
    With no match it proposes k repeats of the pending token (free, and
    exactly right once greedy decoding enters a period-1 loop). Drafts
    only ever affect SPEED — the verify forward recomputes the greedy
    oracle at every position."""
    S = history.shape[0]
    j = jnp.arange(S - 1)
    prev = history[jnp.maximum(pos - 1, 0)]
    cur = history[pos]
    # candidate j: bigram at (j, j+1) strictly before the pending bigram
    m = (j + 1 < pos) & (history[:-1] == prev) & (history[1:] == cur)
    jstar = jnp.argmax(jnp.where(m, j, -1))
    # matched continuation spans [jstar+2, pos] — period >= 1 always,
    # and cycling it keeps every read at or below the pending position
    period = jnp.maximum(pos - jstar - 1, 1)
    idx = jstar + 2 + jnp.arange(k) % period
    cont = history[jnp.clip(idx, 0, S - 1)]
    return jnp.where(jnp.any(m), cont,
                     jnp.full((k,), cur, history.dtype)).astype(jnp.int32)


def _speculative_verify(params, n_heads, caches, tokens, drafts, positions,
                        attn_impl="dense", page_tokens=DEFAULT_PAGE_TOKENS,
                        kernel_impl=None, kernel_interpret=False):
    """Verify ``k`` drafts per lane in ONE batched causal forward.

    ``tokens`` [B] are the pending tokens, ``drafts`` [B, k] the
    proposals, ``positions`` [B] each lane's next KV write index. The
    k+1 ids run through ``_forward_chunk`` (each position attends to the
    cache plus the draft prefix before it — exactly what sequential
    decode would have seen IF every earlier draft was correct), giving
    the greedy ``oracle`` [B, k+1] at all positions. ``accepted`` [B]
    counts the leading drafts that matched their oracle; everything the
    caller emits comes from ``oracle``, so a wrong draft can never
    change output — only how many tokens this step yields. Rejected
    drafts leave stale KV above the accepted point, which the NEXT
    step's k+1 writes fully overwrite (the stale range [new_pos,
    old_pos+k] always sits inside the next write window), so "rollback"
    is nothing more than advancing ``positions`` by accepted+1."""
    tr = params["params"]["transformer"]
    k = drafts.shape[1]
    ids = jnp.concatenate([tokens[:, None], drafts], axis=1)     # [B, k+1]
    h, caches = _forward_chunk(params, n_heads, caches, ids, positions,
                               attn_impl=attn_impl,
                               page_tokens=page_tokens,
                               kernel_impl=kernel_impl,
                               kernel_interpret=kernel_interpret)
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    oracle = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [B, k+1]
    ok = (drafts == oracle[:, :k]).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)          # [B]
    return oracle, accepted, caches


def _forward_full(params, ids, true_len, n_layers, n_heads, head_dim, total,
                  attn_impl="dense", page_tokens=DEFAULT_PAGE_TOKENS,
                  kernel_impl=None, kernel_interpret=False):
    """Single-pass full-sequence causal prefill: every K/V for the
    (padded) prompt ``ids`` [B, S] computed in ONE batched forward into a
    fresh ``total``-long cache, with the logits selected at the true last
    prompt position (``true_len`` — scalar or [B], traced) so padding is
    invisible to the emitted token. Replaces the sequential scan prefill
    (``_prefill``, kept as the parity reference) on every live path:
    ``generate()``, ``beam_search()``, and the serving engine.

    Non-dense backends need page-aligned shapes: sparse pads the prompt
    to a whole number of pages (pad queries write KV past ``true_len``
    that decode overwrites in order before it can ever be attended) and
    allocates at least one full window of cache so the window slice
    always fits; flash rounds the cache length up so it splits into
    whole key blocks. Logit selection at ``true_len - 1`` keeps all of
    it invisible to the emitted token."""
    B, S = ids.shape
    tr = params["params"]["transformer"]
    dtype = _cache_dtype(params)
    cache_len = total
    if attn_impl in ("sparse_xla", "pallas_sparse"):
        pt = int(page_tokens)
        cache_len = max(_round_up(total, pt), (SPARSE_BAND + 1) * pt)
        ids = jnp.pad(ids, ((0, 0), (0, _round_up(S, pt) - S)))
    elif attn_impl in ("flash", "pallas_decode"):
        pt = int(page_tokens)
        cache_len = max(_round_up(total, pt), pt)
    shape = (n_layers, B, n_heads, cache_len, head_dim)
    caches = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    h, caches = _forward_chunk(params, n_heads, caches, ids,
                               jnp.zeros((B,), jnp.int32),
                               attn_impl=attn_impl, page_tokens=page_tokens,
                               kernel_impl=kernel_impl,
                               kernel_interpret=kernel_interpret)
    idx = jnp.clip(jnp.broadcast_to(
        jnp.asarray(true_len, jnp.int32) - 1, (B,)), 0, S - 1)
    h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
    h_last = _ln(h_last, tr["ln_f"])
    last_logits = h_last @ logits_table(tr["wte"], h_last.dtype).T
    return caches, last_logits


@partial(jax.jit, static_argnames=("n_layers", "n_heads", "head_dim",
                                   "max_new_tokens", "greedy", "filtered",
                                   "attn_impl", "page_tokens",
                                   "kernel_impl", "kernel_interpret"))
def _generate_jit(params, prompt_ids, n_layers, n_heads, head_dim,
                  max_new_tokens, greedy, filtered, temperature, top_k,
                  top_p, rng, attn_impl="dense",
                  page_tokens=DEFAULT_PAGE_TOKENS, kernel_impl=None,
                  kernel_interpret=False):
    B, S = prompt_ids.shape
    total = S + max_new_tokens
    caches, last_logits = _forward_full(
        params, prompt_ids, S, n_layers, n_heads, head_dim, total,
        attn_impl=attn_impl, page_tokens=page_tokens,
        kernel_impl=kernel_impl, kernel_interpret=kernel_interpret)

    def decode_body(carry, pos):
        caches, logits, rng = carry
        if greedy:
            token = jnp.argmax(logits, axis=-1)
        else:
            # temperature/top_k/top_p are TRACED operands: sweeping them
            # reuses one compiled program instead of recompiling per
            # value. ``filtered`` is STATIC so plain temperature sampling
            # never pays the per-token argsort/cumsum machinery.
            rng, sub = jax.random.split(rng)
            scaled = logits.astype(jnp.float32) / temperature
            if filtered:
                # temperature FIRST: the nucleus is taken over the
                # distribution actually sampled (HF warper order)
                scaled = filter_logits(scaled, top_k, top_p)
            token = jax.random.categorical(sub, scaled, axis=-1)
        if attn_impl == "sparse_xla":
            logits, caches = _step_window(params, n_heads, caches, token,
                                          pos, page_tokens)
        elif attn_impl == "pallas_sparse":
            logits, caches = _step_window_kernel(
                params, n_heads, caches, token, pos, page_tokens,
                kernel_impl, kernel_interpret)
        elif attn_impl == "pallas_decode":
            logits, caches = _step_kernel(
                params, n_heads, caches, token, pos, page_tokens,
                kernel_impl, kernel_interpret)
        else:
            # flash decode IS dense decode: a single query against the
            # whole cache has no blockwise savings, and the dense step
            # is already one fused einsum
            logits, caches = _step(params, n_heads, caches, token, pos)
        return (caches, logits, rng), token

    (_, _, _), tokens = jax.lax.scan(
        decode_body, (caches, last_logits, rng), jnp.arange(S, total))
    return jnp.swapaxes(tokens, 0, 1)                            # [B, T_new]


def generate(params, config, prompt_ids, max_new_tokens, temperature=0.0,
             rng=None, top_k=0, top_p=1.0, attn_impl="dense",
             kv_page_tokens=None, attention_kernel=None,
             kernel_interpret=None):
    """Generate ``max_new_tokens`` continuations of ``prompt_ids`` [B, S].

    ``temperature=0`` -> greedy argmax; otherwise categorical sampling
    with ``rng`` (required), optionally filtered by ``top_k`` (keep the k
    best ids; 0 disables) and/or ``top_p`` (nucleus: smallest set with
    cumulative probability >= top_p; 1.0 disables). The knob VALUES are
    traced (sweeps share a program); crossing the filters-disabled /
    enabled boundary is one extra compile (static, keeps plain sampling
    off the argsort path). Returns [B, max_new_tokens]. One compiled
    program per (config, shapes, greedy-vs-sampling, filtering on/off).

    For the kernel-tier backends (``pallas_decode``/``pallas_sparse``)
    ``attention_kernel`` forces "pallas"/"xla" (None = the registry's
    probe result) and ``kernel_interpret`` forces interpret mode (None =
    auto: interpret everywhere but real TPU); both resolve through
    `kernels.get_registry()` and become jit statics — a failed probe
    raises ``KernelProbeError`` on a TPU backend and degrades to the
    XLA twin elsewhere."""
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature != 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if top_k < 0 or top_k > config.vocab_size:
        raise ValueError(f"top_k must be in [0, {config.vocab_size}], "
                         f"got {top_k}")
    if not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if attn_impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"attn_impl must be one of {ATTENTION_IMPLS}, got {attn_impl!r}")
    if kv_page_tokens is not None and (
            isinstance(kv_page_tokens, bool)
            or not isinstance(kv_page_tokens, int) or kv_page_tokens < 1):
        raise ValueError(
            f"kv_page_tokens must be an int >= 1, got {kv_page_tokens!r}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if max_new_tokens < 1:
        # beam_search already rejects this; here a zero/negative count
        # would silently scan nothing and return an empty [B, 0] array
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    total = prompt_ids.shape[1] + int(max_new_tokens)
    if total > config.max_position_embeddings:
        # JAX clamps out-of-bounds gathers, so an oversized sequence would
        # silently reuse the last position embedding — fail loudly instead
        raise ValueError(
            f"prompt ({prompt_ids.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds "
            f"max_position_embeddings={config.max_position_embeddings}")
    k_impl, k_interp = None, False
    if attn_impl in KERNEL_ATTENTION_IMPLS:
        from deepspeed_tpu import kernels  # lazy: kernels imports us
        k_impl, k_interp = kernels.resolve(attn_impl,
                                           requested=attention_kernel,
                                           interpret=kernel_interpret)
        kernels.record_call(kernels.kernel_for_backend(attn_impl), k_impl)
    elif attention_kernel is not None:
        raise ValueError(
            f"attention_kernel applies only to {KERNEL_ATTENTION_IMPLS}, "
            f"not attn_impl={attn_impl!r}")
    return _generate_jit(
        params, prompt_ids, config.num_hidden_layers,
        config.num_attention_heads,
        config.hidden_size // config.num_attention_heads,
        int(max_new_tokens), temperature == 0.0,
        top_k > 0 or top_p < 1.0,
        jnp.asarray(max(temperature, 1e-8), jnp.float32),
        jnp.asarray(int(top_k), jnp.int32),
        jnp.asarray(float(top_p), jnp.float32), rng,
        attn_impl=attn_impl,
        page_tokens=int(kv_page_tokens or DEFAULT_PAGE_TOKENS),
        kernel_impl=k_impl, kernel_interpret=bool(k_interp))


def greedy_generate(params, config, prompt_ids, max_new_tokens):
    return generate(params, config, prompt_ids, max_new_tokens)
